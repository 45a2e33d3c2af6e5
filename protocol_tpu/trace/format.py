"""Cluster flight-recorder trace format: versioned, gzip-framed,
append-only.

A trace is the complete, bit-reproducible record of one scheduler-seam
workload: one epoch SNAPSHOT frame (the full columnar marketplace plus
every solve parameter, exactly the wire-v2 ``AssignRequestV2`` the seam
itself ships), then per-tick DELTA frames (churned provider/task rows as
full row replacements — the wire-v2 ``AssignDeltaRequest`` shape — plus
optional heartbeat/node-lifecycle events) and OUTCOME frames (the solve's
assignments, carried duals, and per-phase timings/wire-byte counters from
``SeamMetrics``). Anything the solve consumes rides the trace; replaying
it through any engine reproduces the recorded matching bit-for-bit or
localizes the first divergent tick.

File layout (all integers little-endian)::

    magic   b"PTTRACE1"                                (8 bytes)
    frame*  u8 kind | u8 flags | u32 len | u32 crc32   (10-byte header)
            payload[len]                               (deflate if flags&1)

Frames are written fully and flushed one at a time, so a killed run
always leaves a valid prefix: the reader stops at a truncated header, a
short payload, or a CRC mismatch and reports ``truncated=True`` instead
of raising — the surviving ticks replay normally. Compression is
per-frame DEFLATE (zlib): deterministic bytes (no gzip mtime header), so
recording the same workload twice produces byte-identical files. A
payload longer than ``DEFLATE_CHUNK`` is DEFLATEd in chunks on a thread
pool and still lands as one zlib stream (``FrameDeflater``), so readers
need nothing but ``zlib.decompress``.

Frame payloads reuse the wire-v2 ``TensorBlob`` codecs verbatim
(``protocol_tpu/proto/wire.py``): columns are C-order little-endian raw
bytes with the dtype asserted once at decode. The canonical per-column
dtypes are restated here as ``P_TRACE_DTYPES``/``R_TRACE_DTYPES`` —
traces persist on disk across code revisions, so the trace codec carries
its OWN copy of the table, and the ``dtype-contract`` lint
(scripts/lints/dtype_contract.py) cross-checks all three sites (wire,
arena, trace) column-for-column.
"""

from __future__ import annotations

import dataclasses
import json
import os
import struct
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional

import numpy as np

from protocol_tpu.proto import scheduler_pb2 as pb
from protocol_tpu.proto import wire

MAGIC = b"PTTRACE1"
VERSION = 1

# frame kinds
KIND_META = 1      # JSON: trace provenance + generator knobs
KIND_SNAPSHOT = 2  # pb.SnapshotChunk: epoch header + AssignRequestV2 payload
KIND_DELTA = 3     # u32 n | pb.AssignDeltaRequest[n] | JSON events
KIND_OUTCOME = 4   # u32 n | pb.AssignResponseV2[n] | JSON {tick, metrics}
KIND_EVENT = 5     # JSON {tick, events}: out-of-band structured events
#                    (SLO burn-rate alerts) — NOT solve inputs, so the
#                    replayer ignores them; old readers skip the kind
KIND_ARENA = 6     # named-ndarray pack (pack_arrays): carried solver
#                    state — used by the session CHECKPOINT files
#                    (faults/checkpoint.py), never by workload traces;
#                    the replayer skips the kind by the unknown-kind
#                    contract

_FLAG_DEFLATE = 1
COMPRESSLEVEL = 6
# A frame's payload is DEFLATEd in chunks of this many bytes, cut at
# fixed offsets from its first byte, each a stream of its own on a
# shared pool of threads (``FrameDeflater``). A chunk starts with an
# empty window, so each cut costs what the next chunk's first 32 KiB
# could have matched behind it. One warm tick's SNAPSHOT + ARENA
# payloads at 8,192 x 4,915 rows (13,004,932 B raw, level 1), fed as
# the checkpoint's worker feeds them, on four threads of the host of a
# TPU v5e (``scripts/ckpt_deflate_levels.py --chunk``; best wall of
# three; zlib 1.2.13):
#
#   chunk     wall ms   zlib ms   bytes out   chunks
#   256 KiB   45.9      169.4     4,552,835   51
#   512 KiB   43.0      163.3     4,554,848   26
#   1 MiB     41.8      150.4     4,556,815   14
#   2 MiB     43.0      151.8     4,559,404   7
#   4 MiB     63.7      148.9     4,560,296   4
#   one stream          145.8     4,560,016   1
#
# 1 MiB is the shortest wall; smaller chunks spend more inside zlib,
# larger ones leave threads idle at the end. The cuts cost no bytes
# here (-0.07% against one stream).
DEFLATE_CHUNK = 1 << 20
_HEADER = struct.Struct("<BBII")

# Canonical trace-frame column dtypes. These MUST match the wire tables
# (proto/wire.py) column-for-column: the dtype-contract lint enforces it
# statically and _check_tables() enforces it at import. The duplication
# is deliberate — a trace on disk is decoded by THIS table, so a wire
# revision that drifts a column fails loudly here instead of silently
# reinterpreting archived bytes.
P_TRACE_DTYPES: dict[str, np.dtype] = {
    "gpu_count": np.dtype(np.int32),
    "gpu_mem_mb": np.dtype(np.int32),
    "gpu_model_id": np.dtype(np.int32),
    "has_gpu": np.dtype(np.bool_),
    "has_cpu": np.dtype(np.bool_),
    "cpu_cores": np.dtype(np.int32),
    "ram_mb": np.dtype(np.int32),
    "storage_gb": np.dtype(np.int32),
    "lat": np.dtype(np.float32),
    "lon": np.dtype(np.float32),
    "has_location": np.dtype(np.bool_),
    "price": np.dtype(np.float32),
    "load": np.dtype(np.float32),
    "valid": np.dtype(np.bool_),
}
R_TRACE_DTYPES: dict[str, np.dtype] = {
    "cpu_required": np.dtype(np.bool_),
    "cpu_cores": np.dtype(np.int32),
    "ram_mb": np.dtype(np.int32),
    "storage_gb": np.dtype(np.int32),
    "gpu_opt_valid": np.dtype(np.bool_),
    "gpu_count": np.dtype(np.int32),
    "gpu_mem_min": np.dtype(np.int32),
    "gpu_mem_max": np.dtype(np.int32),
    "gpu_total_mem_min": np.dtype(np.int32),
    "gpu_total_mem_max": np.dtype(np.int32),
    "gpu_model_mask": np.dtype(np.uint32),
    "gpu_model_constrained": np.dtype(np.bool_),
    "lat": np.dtype(np.float32),
    "lon": np.dtype(np.float32),
    "has_location": np.dtype(np.bool_),
    "priority": np.dtype(np.float32),
    "valid": np.dtype(np.bool_),
}


def _check_tables() -> None:
    # runtime twin of the dtype-contract lint's cross-check
    for name, mine, theirs in (
        ("P", P_TRACE_DTYPES, wire.P_WIRE_DTYPES),
        ("R", R_TRACE_DTYPES, wire.R_WIRE_DTYPES),
    ):
        if list(mine.items()) != list(theirs.items()):
            raise AssertionError(
                f"{name}_TRACE_DTYPES drifted from the wire table — archived "
                "traces would decode at the wrong widths"
            )


# ---------------- named-ndarray pack (ARENA frames) ----------------


def pack_plan(
    named: dict[str, Optional[np.ndarray]], last: tuple = ()
) -> tuple[bytes, list]:
    """The two halves of :func:`pack_arrays`: the length-prefixed
    manifest, and the contiguous arrays as ``(name, array)`` in the
    order their buffers follow it. The manifest needs dtypes and shapes
    only, so it can be written before the ``last`` arrays hold their
    values."""
    manifest: dict = {}
    arrays: list = []
    off = 0
    order = sorted(n for n in named if n not in last)
    order += sorted(n for n in named if n in last)
    for name in order:
        a = named[name]
        if a is None:
            manifest[name] = None
            continue
        a = np.ascontiguousarray(a)
        manifest[name] = {
            "dtype": a.dtype.name,
            "shape": list(a.shape),
            "offset": off,
        }
        arrays.append((name, a))
        off += a.nbytes
    head = json.dumps(manifest, sort_keys=True).encode()
    return struct.pack("<I", len(head)) + head, arrays


def raw_bytes(a: np.ndarray) -> np.ndarray:
    """A contiguous array as the bytes ``tobytes`` would copy, not
    copied."""
    return a.reshape(-1).view(np.uint8)


def pack_arrays(
    named: dict[str, Optional[np.ndarray]], last: tuple = ()
) -> bytes:
    """Deterministic bytes for a dict of (optionally None) ndarrays:
    a sorted JSON manifest (name -> dtype/shape/offset) followed by the
    C-order little-endian raw buffers, sorted by name with the names in
    ``last`` after all others (a reader goes by ``offset``, so the
    order of the buffers is the writer's to choose: a checkpoint puts
    what its solve writes at the end, and can stream the rest out
    before the solve is done). The checkpoint codec — same
    byte-exactness contract as the TensorBlob columns, without protobuf
    in the way (carried solver state is not a wire message)."""
    head, arrays = pack_plan(named, last)
    return head + b"".join(a.tobytes() for _, a in arrays)


def unpack_arrays(payload: bytes) -> dict[str, Optional[np.ndarray]]:
    """Inverse of :func:`pack_arrays`. Raises ValueError on a short or
    inconsistent payload (a torn checkpoint must fail loudly at load,
    never decode at the wrong widths)."""
    if len(payload) < 4:
        raise ValueError("array pack too short for its header")
    (n,) = struct.unpack_from("<I", payload)
    head = payload[4:4 + n]
    if len(head) < n:
        raise ValueError("array pack manifest truncated")
    manifest = json.loads(head)
    base = 4 + n
    out: dict[str, Optional[np.ndarray]] = {}
    for name, m in manifest.items():
        if m is None:
            out[name] = None
            continue
        dt = np.dtype(m["dtype"])
        shape = tuple(int(s) for s in m["shape"])
        count = int(np.prod(shape)) if shape else 1
        start = base + int(m["offset"])
        end = start + count * dt.itemsize
        if end > len(payload):
            raise ValueError(f"array pack buffer {name!r} truncated")
        out[name] = np.frombuffer(
            payload[start:end], dtype=dt
        ).reshape(shape)
    return out


# ---------------- frame records ----------------


@dataclasses.dataclass
class DeltaRecord:
    """One recorded tick's inputs: churned rows + lifecycle events."""

    tick: int
    provider_rows: np.ndarray  # i32 [n]
    p_cols: dict[str, np.ndarray]  # churned rows only, trace dtypes
    task_rows: np.ndarray
    r_cols: dict[str, np.ndarray]
    events: list


@dataclasses.dataclass
class OutcomeRecord:
    """One recorded tick's solve result + provenance metrics."""

    tick: int
    provider_for_task: np.ndarray  # i32 [T]
    price: Optional[np.ndarray]  # f32 [P] (carried duals), may be absent
    num_assigned: int
    metrics: dict  # per-phase ms, wire bytes, arena stats


@dataclasses.dataclass
class Snapshot:
    """The epoch: full columns + every solve parameter."""

    trace_id: str
    fingerprint: str
    p_cols: dict[str, np.ndarray]
    r_cols: dict[str, np.ndarray]
    weights: tuple  # (price, load, proximity, priority) f32
    kernel: str
    top_k: int
    eps: float
    max_iters: int

    @property
    def n_providers(self) -> int:
        return int(self.p_cols["gpu_count"].shape[0])

    @property
    def n_tasks(self) -> int:
        return int(self.r_cols["cpu_cores"].shape[0])

    def request_v2(self) -> pb.AssignRequestV2:
        """Re-pack as the wire message (what the snapshot frame holds)."""
        return pb.AssignRequestV2(
            providers=wire.encode_providers_v2(
                _as_ns(self.p_cols)
            ),
            requirements=wire.encode_requirements_v2(
                _as_ns(self.r_cols)
            ),
            weights=pb.CostWeights(
                price=self.weights[0], load=self.weights[1],
                proximity=self.weights[2], priority=self.weights[3],
            ),
            kernel=self.kernel, top_k=self.top_k, eps=self.eps,
            max_iters=self.max_iters,
        )


@dataclasses.dataclass
class Trace:
    """A parsed trace: meta + snapshot + per-tick delta/outcome records."""

    path: str
    meta: dict
    snapshot: Optional[Snapshot]
    deltas: list  # DeltaRecord, tick order
    outcomes: list  # OutcomeRecord, tick order (tick 0 = snapshot solve)
    truncated: bool
    n_frames: int
    # EVENT frames ({tick, events}, e.g. SLO alerts) — observational
    # side channel, never replay input
    events: list = dataclasses.field(default_factory=list)

    @property
    def ticks(self) -> int:
        """Input ticks: the snapshot plus every delta frame."""
        return (1 if self.snapshot is not None else 0) + len(self.deltas)

    def outcome_for(self, tick: int) -> Optional[OutcomeRecord]:
        # index built lazily: replay verifies one lookup per tick, and a
        # linear scan would make a 16k-tick verification O(ticks^2)
        by_tick = self.__dict__.get("_outcome_by_tick")
        if by_tick is None or len(by_tick) != len(self.outcomes):
            by_tick = {o.tick: o for o in self.outcomes}
            self.__dict__["_outcome_by_tick"] = by_tick
        return by_tick.get(tick)


def _as_ns(cols: dict[str, np.ndarray]):
    ns = type("_Cols", (), {})()
    for name, arr in cols.items():
        setattr(ns, name, arr)
    return ns


# ---------------- writer ----------------


def snapshot_payload(
    trace_id: str, fingerprint: str, request: pb.AssignRequestV2
) -> bytes:
    """A SNAPSHOT frame's payload before DEFLATE."""
    payload = request.SerializeToString()
    return pb.SnapshotChunk(
        session_id=trace_id, epoch_fingerprint=fingerprint,
        payload=payload, total_bytes=len(payload),
    ).SerializeToString()


_ADLER_BASE = 65521


def adler32_combine(first: int, second: int, second_len: int) -> int:
    """The Adler-32 of two byte strings joined, from the Adler-32 of
    each and the second's length (zlib's ``adler32_combine``, which
    Python's ``zlib`` does not export)."""
    a1, b1 = first & 0xFFFF, first >> 16
    a2, b2 = second & 0xFFFF, second >> 16
    a = (a1 + a2 - 1) % _ADLER_BASE
    b = (b1 + b2 + second_len * (a1 - 1)) % _ADLER_BASE
    return (b << 16) | a


class _Chunk:
    """One chunk of a frame's payload: the views of the fed buffers
    that fall in it, and a DEFLATE stream of its own, zlib-wrapped for
    the frame's first chunk (it writes the 2-byte header) and raw for
    every later one. One thread at a time uses it: the feeding thread
    until the chunk is handed to the pool, then one pool task."""

    __slots__ = ("first", "z", "parts", "pushed", "size", "out", "adler",
                 "ms")

    def __init__(self, level: int, first: bool):
        self.first = first
        wbits = zlib.MAX_WBITS if first else -zlib.MAX_WBITS
        self.z = zlib.compressobj(level, zlib.DEFLATED, wbits)
        self.parts: list = []
        self.pushed = 0  # parts already through the stream
        self.size = 0
        self.out: list = []
        self.adler = 1
        self.ms = 0.0  # time inside zlib not yet taken by the deflater

    def push(self) -> None:
        t0 = time.perf_counter()
        for part in self.parts[self.pushed:]:
            self.out.append(self.z.compress(part))
        self.pushed = len(self.parts)
        self.ms += (time.perf_counter() - t0) * 1e3

    def close(self, last: bool) -> None:
        """End the stream: ``Z_FINISH`` for the frame's last chunk,
        else ``Z_SYNC_FLUSH`` (byte-aligned and not final, so the next
        chunk's blocks follow it). The Adler-32 of a chunk that is not
        the whole payload is taken here, for the stream's trailer."""
        self.push()
        t0 = time.perf_counter()
        self.out.append(
            self.z.flush(zlib.Z_FINISH if last else zlib.Z_SYNC_FLUSH)
        )
        if not (self.first and last):
            for part in self.parts:
                self.adler = zlib.adler32(part, self.adler)
        self.ms += (time.perf_counter() - t0) * 1e3
        self.z = None


def _deflate_pool() -> ThreadPoolExecutor:
    # no thread starts before the first chunk is handed over: a process
    # whose frames all fit one chunk never starts one
    return ThreadPoolExecutor(
        max_workers=min(4, os.cpu_count() or 1), thread_name_prefix="deflate"
    )


_POOL = _deflate_pool()


def _renew_pool() -> None:
    # a forked child holds the parent's pool object but none of its
    # threads: work handed to it would never run
    global _POOL
    _POOL = _deflate_pool()


os.register_at_fork(after_in_child=_renew_pool)


class FrameDeflater:
    """One frame's payload DEFLATEd buffer by buffer, in chunks of
    ``DEFLATE_CHUNK`` bytes cut at fixed offsets from the payload's
    first byte, each chunk a DEFLATE stream of its own on a thread pool
    every deflater of the process shares (zlib releases the GIL). The
    body is one zlib stream: the first chunk's header and blocks, every
    chunk ended by a sync flush and the last by ``Z_FINISH``, and the
    Adler-32 of the whole payload, combined from the chunks'. Any
    ``zlib.decompress`` reads it. The cuts depend on the payload alone,
    not on how it was fed, and a chunk's stream does not depend on how
    its bytes were fed either, so the body is the piecewise stream of
    the joined payload however it arrived; a payload of one chunk or
    less is exactly ``zlib.compress(payload, compresslevel)``. A frame
    can be started before its last bytes exist, and on another thread.
    Buffers are bytes-like and are kept by reference, not copied, until
    :meth:`finish`."""

    def __init__(self, compresslevel: int = COMPRESSLEVEL):
        self.compresslevel = compresslevel
        self._buffers: list = []
        self._open = _Chunk(compresslevel, first=True)
        # chunks handed to the pool, in payload order, and how many of
        # them have been waited for
        self._sent: list = []
        self._waited = 0
        self._done: Optional[tuple] = None
        self._ms = 0.0
        self.bytes_raw = 0
        self.chunks = 0  # the finished stream's chunks

    def feed(self, buf) -> None:
        view = memoryview(buf).cast("B")
        self._buffers.append(buf)
        self.bytes_raw += view.nbytes
        while view.nbytes:
            room = DEFLATE_CHUNK - self._open.size
            if room == 0:
                # full, and more bytes follow: not the frame's last chunk
                self._sent.append(
                    (self._open, _POOL.submit(self._open.close, False))
                )
                self._open = _Chunk(self.compresslevel, first=False)
                continue
            part = view[:room]
            self._open.parts.append(part)
            self._open.size += part.nbytes
            view = view[room:]

    def _take(self, chunk: _Chunk) -> None:
        self._ms += chunk.ms
        chunk.ms = 0.0

    def _wait(self) -> None:
        for chunk, future in self._sent[self._waited:]:
            future.result()
            self._take(chunk)
        self._waited = len(self._sent)

    def settle(self) -> None:
        """DEFLATE all that was fed so far, the chunk still open on
        this thread and the full ones on the pool, and wait for it:
        :meth:`finish` is left what is fed after this call and the
        stream's end."""
        self._open.push()
        self._take(self._open)
        self._wait()

    def finish(self) -> tuple[int, bytes]:
        """``(flags, body)`` as a frame stores them: the stream where
        it is shorter than the payload, else the payload itself."""
        if self._done is None:
            last = self._open
            last.close(True)
            self._take(last)
            self._wait()
            chunks = [chunk for chunk, _ in self._sent] + [last]
            out = [b for chunk in chunks for b in chunk.out]
            if len(chunks) > 1:
                adler = chunks[0].adler
                for chunk in chunks[1:]:
                    adler = adler32_combine(adler, chunk.adler, chunk.size)
                out.append(struct.pack(">I", adler))
            z = b"".join(out)
            self.chunks = len(chunks)
            if len(z) < self.bytes_raw:
                self._done = (_FLAG_DEFLATE, z)
            else:
                self._done = (0, b"".join(self._buffers))
            self._buffers, self._sent, self._open = [], [], None
        return self._done

    def take_ms(self) -> float:
        """Time inside zlib since the last call, in ms: the sum of the
        chunks' times, whichever threads ran them (waited for by
        :meth:`settle` or :meth:`finish`)."""
        ms, self._ms = self._ms, 0.0
        return ms


class TraceWriter:
    """Append-only frame writer. Every ``write_*`` call lands one fully
    flushed frame, so a SIGKILL can never lose more than the frame being
    written (the reader tolerates that torn tail)."""

    def __init__(self, path: str, meta: Optional[dict] = None,
                 compresslevel: int = COMPRESSLEVEL):
        _check_tables()
        self.path = path
        self.compresslevel = compresslevel
        # what this writer has cost so far (a checkpoint's writer lives
        # for one flush): payload bytes before DEFLATE, bytes in the
        # file, the time inside zlib and the chunks DEFLATEd
        self.bytes_raw = 0
        self.bytes_out = len(MAGIC)
        self.deflate_ms = 0.0
        self.deflate_chunks = 0
        self._fh = open(path, "wb")
        self._fh.write(MAGIC)
        m = {"version": VERSION}
        m.update(meta or {})
        self._frame(KIND_META, json.dumps(m, sort_keys=True).encode())

    def _frame(self, kind: int, payload: bytes) -> None:
        deflated = FrameDeflater(self.compresslevel)
        deflated.feed(payload)
        self._frame_deflated(kind, deflated)

    def _put(self, kind: int, flags: int, body: bytes, raw_len: int) -> None:
        self.bytes_raw += raw_len
        self._fh.write(
            _HEADER.pack(kind, flags, len(body), zlib.crc32(body))
        )
        self._fh.write(body)
        self._fh.flush()
        self.bytes_out += _HEADER.size + len(body)

    def _frame_deflated(self, kind: int, deflated: "FrameDeflater") -> None:
        """Land a frame whose payload was fed to ``deflated`` (what
        :meth:`_frame` does for a payload it is handed whole, so a
        frame's bytes do not depend on which path fed it). The zlib
        time the deflater has spent since its last ``take_ms``, and
        its chunks, count as this writer's."""
        if deflated.compresslevel != self.compresslevel:
            raise ValueError("frame deflated at another level")
        flags, body = deflated.finish()
        self.deflate_ms += deflated.take_ms()
        self.deflate_chunks += deflated.chunks
        self._put(kind, flags, body, deflated.bytes_raw)

    def write_snapshot(
        self, trace_id: str, fingerprint: str,
        request: Optional[pb.AssignRequestV2],
        deflated: Optional["FrameDeflater"] = None,
    ) -> None:
        """``deflated``: a deflater that was fed
        :func:`snapshot_payload` of these arguments elsewhere (its
        stream is what lands; ``request`` is not read)."""
        if deflated is not None:
            self._frame_deflated(KIND_SNAPSHOT, deflated)
            return
        self._frame(
            KIND_SNAPSHOT, snapshot_payload(trace_id, fingerprint, request)
        )

    def write_delta(
        self, delta: pb.AssignDeltaRequest, events: Optional[list] = None
    ) -> None:
        body = delta.SerializeToString()
        ev = json.dumps(events or [], sort_keys=True).encode()
        self._frame(KIND_DELTA, struct.pack("<I", len(body)) + body + ev)

    def write_delta_cols(
        self,
        tick: int,
        provider_rows: np.ndarray,
        p_cols: Optional[dict[str, np.ndarray]],
        task_rows: np.ndarray,
        r_cols: Optional[dict[str, np.ndarray]],
        events: Optional[list] = None,
    ) -> None:
        """Column-dict convenience front end over :meth:`write_delta`."""
        req = pb.AssignDeltaRequest(tick=tick)
        if provider_rows is not None and provider_rows.size:
            req.provider_rows.CopyFrom(wire.blob(provider_rows, np.int32))
            req.providers.CopyFrom(wire.encode_providers_v2(_as_ns(p_cols)))
        if task_rows is not None and task_rows.size:
            req.task_rows.CopyFrom(wire.blob(task_rows, np.int32))
            req.requirements.CopyFrom(
                wire.encode_requirements_v2(_as_ns(r_cols))
            )
        self.write_delta(req, events)

    def write_events(self, tick: int, events: list) -> None:
        """Out-of-band structured events (SLO burn-rate alerts) tied to
        a tick. Never a solve input: the replayer skips EVENT frames,
        and pre-EVENT readers skip the unknown kind by contract."""
        self._frame(
            KIND_EVENT,
            json.dumps(
                {"tick": int(tick), "events": list(events)}, sort_keys=True
            ).encode(),
        )

    def write_arena(
        self, named: dict[str, Optional[np.ndarray]], last: tuple = (),
        deflated: Optional["FrameDeflater"] = None,
    ) -> None:
        """Carried solver state as one ARENA frame (checkpoint files;
        workload traces never carry one — the replayer skips the
        kind). ``last`` as in :func:`pack_arrays`. ``deflated``: a
        deflater that was fed the manifest of ``pack_plan(named,
        last)`` and every buffer not in ``last`` elsewhere; the
        ``last`` buffers are fed here and its stream lands."""
        if deflated is None:
            self._frame(KIND_ARENA, pack_arrays(named, last))
            return
        for name, a in pack_plan(named, last)[1]:
            if name in last:
                deflated.feed(raw_bytes(a))
        self._frame_deflated(KIND_ARENA, deflated)

    def write_outcome(
        self,
        tick: int,
        provider_for_task: np.ndarray,
        price: Optional[np.ndarray] = None,
        metrics: Optional[dict] = None,
    ) -> None:
        resp = pb.AssignResponseV2(
            provider_for_task=wire.blob(provider_for_task, np.int32),
            num_assigned=int((np.asarray(provider_for_task) >= 0).sum()),
        )
        if price is not None:
            resp.price.CopyFrom(wire.blob(price, np.float32))
        body = resp.SerializeToString()
        tail = json.dumps(
            {"tick": int(tick), "metrics": metrics or {}}, sort_keys=True
        ).encode()
        self._frame(KIND_OUTCOME, struct.pack("<I", len(body)) + body + tail)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------- reader ----------------


def read_frames(path: str) -> Iterator[tuple[int, bytes]]:
    """Yield (kind, payload) per intact frame; a torn tail (truncated
    header/payload, CRC mismatch) ends iteration cleanly — the final
    yield is the sentinel ``(-1, b"")`` ONLY when the tail was torn."""
    with open(path, "rb") as fh:
        if fh.read(len(MAGIC)) != MAGIC:
            raise ValueError(f"{path}: not a PTTRACE1 trace file")
        while True:
            head = fh.read(_HEADER.size)
            if not head:
                return  # clean EOF
            if len(head) < _HEADER.size:
                yield -1, b""
                return
            kind, flags, length, crc = _HEADER.unpack(head)
            payload = fh.read(length)
            if len(payload) < length or zlib.crc32(payload) != crc:
                yield -1, b""
                return
            if flags & _FLAG_DEFLATE:
                payload = zlib.decompress(payload)
            yield kind, payload


def _parse_snapshot(payload: bytes) -> Snapshot:
    chunk = pb.SnapshotChunk()
    chunk.ParseFromString(payload)
    req = pb.AssignRequestV2()
    req.ParseFromString(chunk.payload)
    p_cols = wire._decode_columns(req.providers, P_TRACE_DTYPES)
    r_cols = wire._decode_columns(req.requirements, R_TRACE_DTYPES)
    return Snapshot(
        trace_id=chunk.session_id,
        fingerprint=chunk.epoch_fingerprint,
        p_cols=p_cols,
        r_cols=r_cols,
        weights=(
            req.weights.price, req.weights.load,
            req.weights.proximity, req.weights.priority,
        ),
        kernel=req.kernel,
        top_k=int(req.top_k),
        eps=float(req.eps),
        max_iters=int(req.max_iters),
    )


def _parse_delta(payload: bytes) -> DeltaRecord:
    (n,) = struct.unpack_from("<I", payload)
    req = pb.AssignDeltaRequest()
    req.ParseFromString(payload[4:4 + n])
    events = json.loads(payload[4 + n:] or b"[]")
    prow = (
        wire.unblob(req.provider_rows, np.int32)
        if req.HasField("provider_rows") else np.zeros(0, np.int32)
    )
    trow = (
        wire.unblob(req.task_rows, np.int32)
        if req.HasField("task_rows") else np.zeros(0, np.int32)
    )
    p_cols = (
        wire._decode_columns(req.providers, P_TRACE_DTYPES)
        if prow.size else {}
    )
    r_cols = (
        wire._decode_columns(req.requirements, R_TRACE_DTYPES)
        if trow.size else {}
    )
    return DeltaRecord(
        tick=int(req.tick), provider_rows=prow, p_cols=p_cols,
        task_rows=trow, r_cols=r_cols, events=events,
    )


def _parse_outcome(payload: bytes) -> OutcomeRecord:
    (n,) = struct.unpack_from("<I", payload)
    resp = pb.AssignResponseV2()
    resp.ParseFromString(payload[4:4 + n])
    tail = json.loads(payload[4 + n:] or b"{}")
    return OutcomeRecord(
        tick=int(tail.get("tick", -1)),
        provider_for_task=wire.unblob(resp.provider_for_task, np.int32),
        price=(
            wire.unblob(resp.price, np.float32)
            if resp.HasField("price") else None
        ),
        num_assigned=int(resp.num_assigned),
        metrics=tail.get("metrics", {}),
    )


def read_trace(path: str) -> Trace:
    """Parse a trace file. Tolerant of torn tails: whatever frames are
    intact come back, with ``truncated=True`` flagging the tear."""
    _check_tables()
    meta: dict = {}
    snapshot: Optional[Snapshot] = None
    deltas: list[DeltaRecord] = []
    outcomes: list[OutcomeRecord] = []
    events: list = []
    truncated = False
    n_frames = 0
    for kind, payload in read_frames(path):
        if kind == -1:
            truncated = True
            break
        n_frames += 1
        if kind == KIND_META:
            meta = json.loads(payload)
        elif kind == KIND_SNAPSHOT:
            snapshot = _parse_snapshot(payload)
        elif kind == KIND_DELTA:
            deltas.append(_parse_delta(payload))
        elif kind == KIND_OUTCOME:
            outcomes.append(_parse_outcome(payload))
        elif kind == KIND_EVENT:
            events.append(json.loads(payload))
        # unknown kinds are skipped: future writers may append new frame
        # kinds without breaking old readers (the version rides in META)
    return Trace(
        path=path, meta=meta, snapshot=snapshot, deltas=deltas,
        outcomes=outcomes, truncated=truncated, n_frames=n_frames,
        events=events,
    )


def info(path: str) -> dict:
    """Human-facing summary (the ``trace info`` CLI verb)."""
    t = read_trace(path)
    out = {
        "path": path,
        "version": t.meta.get("version"),
        "meta": {k: v for k, v in t.meta.items() if k != "version"},
        "frames": t.n_frames,
        "truncated": t.truncated,
        "ticks": t.ticks,
        "outcomes": len(t.outcomes),
        "events": len(t.events),
    }
    if t.snapshot is not None:
        s = t.snapshot
        delta_rows = sum(
            int(d.provider_rows.size + d.task_rows.size) for d in t.deltas
        )
        out.update(
            providers=s.n_providers, tasks=s.n_tasks, kernel=s.kernel,
            top_k=s.top_k, eps=round(s.eps, 6), fingerprint=s.fingerprint,
            delta_rows_total=delta_rows,
        )
    if t.outcomes:
        out["assigned_last"] = t.outcomes[-1].num_assigned
        solve_ms = [
            o.metrics.get("solve_ms") for o in t.outcomes
            if o.metrics.get("solve_ms") is not None
        ]
        if solve_ms:
            out["mean_solve_ms"] = round(float(np.mean(solve_ms)), 3)
    return out
