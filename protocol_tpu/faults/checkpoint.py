"""Warm session checkpoints: crash-safe per-session journals.

A servicer crash used to destroy every session arena: H clients would
stampede into cold full-snapshot reopens (the herd the fallback ladder
exists to avoid, amplified H-fold at the worst possible moment). The
checkpointer gives each session a compact on-disk twin, flushed on a
tick cadence BEFORE the tick's response is acknowledged, so a restarted
servicer rehydrates every session warm and ``AssignDelta`` resumes at
the checkpointed cursor.

One file per session, reusing the trace container and codecs verbatim
(``PTTRACE1`` framing, SNAPSHOT = the session's padded columns as the
wire's own ``AssignRequestV2``, OUTCOME = the last acknowledged plan,
ARENA = the carried solver state via ``pack_arrays``):

    META      JSON: session identity + solve params + tick cursor +
              dedup CRC + arena cadence cursors
    SNAPSHOT  the session's CURRENT cumulative columns (padded, with
              the valid mask — bit-exact restore, no re-padding drift)
    ARENA     candidate structure + duals + previous matching
              (``NativeSolveArena.export_state``): the candidate lists
              are PATH-DEPENDENT (incremental merges reorder them), so
              without this frame a restart could only continue cold —
              with it, the restored warm chain is bit-identical to the
              uninterrupted one
    OUTCOME   tick + the last plan the client was (or was about to be)
              acknowledged — what idempotent retransmit replays

Writes are crash-atomic (temp file + ``os.replace``) and frames are
individually CRC'd, so a kill mid-flush leaves either the previous
intact checkpoint or a torn temp file nobody reads. A checkpoint that
fails to load (torn, version drift, decode error) is SKIPPED with a
warning: the session's client falls back down the ladder exactly as it
would have without checkpoints — recovery is an optimization, never a
new failure mode.

Overlap (ISSUE 27): almost none of a warm tick's checkpoint depends on
its solve. When a jax arena says its candidate structure is final for
the tick (``JaxSolveArena.structure_hook``, armed by :meth:`arm_locked`
for a tick that is ``due``), a worker thread builds the SNAPSHOT frame
and DEFLATEs the ARENA payload's manifest and solve-independent buffers
while the device runs the auction; the flush joins it, feeds what the
solve wrote, and writes. Every frame, on the worker and in the flush
alike, is DEFLATEd in chunks of ``tfmt.DEFLATE_CHUNK`` (1 MiB) on a
pool of up to four threads the process shares (``tfmt.FrameDeflater``):
a warm tick's 13 MB are 16 chunks, and the worker's wall falls from
one core's level-1 DEFLATE of them to what the pool takes.
The cuts lie at fixed offsets of each payload, wherever the worker's
feeding ends and the flush's begins, so the journal is byte for byte
what the sequential path writes; a frame of one chunk is
``zlib.compress``'s bytes as before. The worker never touches a file,
and the flush takes the prefix only if it was built for this tick from
the very objects the session and arena hold now — anything else (no
prefix, a stale one, a raised one) writes as before, counted.

DEFLATE level (ISSUE 34): a checkpoint's frames are written at
``CKPT_COMPRESSLEVEL`` = 1, a workload trace's at
``trace/format.COMPRESSLEVEL`` = 6. The two files want opposite things
of the same container: a trace is written once, archived and replayed,
and pays for its bytes at rest; a journal is replaced every tick, read
once after a crash, and its DEFLATE stands between a tick's solve and
its ack. The writer of each knows which it writes; nothing in a payload
could tell it. Readers (``read_frames``, the fleet's loaders, the
benchmark's journal walk) call ``zlib.decompress``, which is blind to
the level: a journal written at 6 by an older process loads here and
one written here loads there, so a rolling restart and a ``handoff``
between processes of both versions keep working.

Cadence: ``every=1`` (the default, and what the chaos gate runs)
checkpoints every tick — the zero-reopen guarantee. ``every=N`` trades
durability for throughput: a crash loses up to N-1 ticks and the
affected clients re-open from their authoritative columns (counted,
bounded, explicit).

Namespacing (dfleet): journals are keyed by **(process id, session
id)** — every checkpointer owns ``<root>/<proc_id>/`` and only ever
reads its own namespace, so N servicer processes can share one journal
root (a shared volume) without ever rehydrating each other's live
sessions. Migration rides on this: :meth:`handoff` atomically renames a
journal from this process's namespace into the target's (``os.replace``
— the journal exists in exactly one namespace at every instant), and
the target rehydrates it warm on its next delta miss
(:meth:`load_one`). The post-load ownership re-check closes the
POSIX-fd window where a reader that opened the file just before the
rename could otherwise rehydrate a journal it no longer owns.

Fencing (ISSUE 14): each namespace carries a monotonic **fencing
epoch** (``FENCE.json``, stamped by the fleet manager at spawn and
SUPERSEDED at ejection/orphan-handoff). The checkpointer adopts the
stamp it finds at boot and re-reads the file (stat-cached — one
``os.stat`` per check) on every flush: a higher epoch on disk means
this process was EJECTED while it wasn't looking (SIGSTOP zombie,
partitioned node) and its journals re-routed — the flush REFUSES
(counted), and the servicer answers ``moved:`` instead of acking, so a
resumed zombie can never double-apply a tick or resurrect a journal it
no longer owns. Split-brain is impossible by construction: the PR 12
rule "the journal's location is the authority" becomes "…at the
highest fence". The stamp carries the post-ejection topology so the
zombie's redirects point at each session's REAL new home.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Optional

import numpy as np

from protocol_tpu.obs.spans import TRACER as _tracer
from protocol_tpu.trace import format as tfmt

log = logging.getLogger(__name__)

_META_KIND = "session-checkpoint"
_SUFFIX = ".ckpt"
FENCE_NAME = "FENCE.json"

# The DEFLATE level of every frame of a session checkpoint, on the
# worker (``_PrefixJob``) and in the flush (``_write_locked``) alike:
# ``TraceWriter._frame_deflated`` refuses a deflater of another level
# than its writer's, so the two paths cannot drift apart. One warm
# tick's SNAPSHOT + ARENA payloads of the benchmark's marketplace,
# 13,004,932 B raw at every shape (P and T pad to 8,192), read by
# ``scripts/ckpt_deflate_levels.py`` on the host of a TPU v5e (PR 34's
# chip run; zlib 1.2.13, one core, best of two; ms, MB out):
#
#   shape          level 6       level 1       Z_RLE         stored
#   8,192 x 8,192  809   6.950   225   7.274   130   7.468   5  13.006
#   8,192 x 4,915  511   4.338   146   4.560   101   5.266   6  13.006
#   6,554 x 8,192  754   6.749   217   7.082   125   7.317   6  13.006
#
# Level 6's lazy matching walks long hash chains through int32 index
# lists that hold almost no repeats (``cand_p`` alone: 275 ms against
# 37): 3.5 times level 1's time for 4.7-5.1% of the bytes. Levels 2-4
# cost 266-347 ms at 8,192 x 8,192 for 1.3-2.8% of the bytes back;
# Z_RLE is faster still but misses the padded rows' repeats, four bytes
# apart (+21% at 8,192 x 4,915). Why a workload trace keeps
# ``tfmt.COMPRESSLEVEL`` (6): the module docstring. At this level every
# frame is DEFLATEd in 1 MiB chunks on a pool of four threads
# (``tfmt.DEFLATE_CHUNK``, where the reading that chose the size is):
# the two payloads at 8,192 x 4,915 take 41.8 ms of wall instead of
# 145.8, at 150.4 ms inside zlib, and the 12 cuts between their 14
# chunks cost no bytes (4,556,815 out against one stream's 4,560,016).
CKPT_COMPRESSLEVEL = 1


def fence_path(root: str, proc_id: str) -> str:
    return os.path.join(root, str(proc_id), FENCE_NAME)


def read_fence(root: str, proc_id: str) -> dict:
    """The namespace's current fence stamp: ``{"epoch": int,
    "topology": dict | None}``. Epoch 0 when no stamp exists (the
    pre-dfleet single-process layout) — fencing is inert there."""
    try:
        with open(fence_path(root, proc_id)) as fh:
            d = json.load(fh)
        return {
            "epoch": int(d.get("epoch", 0)),
            "topology": d.get("topology"),
        }
    except (OSError, ValueError):
        return {"epoch": 0, "topology": None}


def stamp_fence(
    root: str,
    proc_id: str,
    epoch: Optional[int] = None,
    topology: Optional[dict] = None,
) -> int:
    """Write the namespace's fence stamp (crash-atomic: temp +
    ``os.replace``). ``epoch=None`` bumps monotonically from whatever
    is on disk — the spawn/ejection callers never need to coordinate a
    counter, the file IS the counter. Returns the stamped epoch."""
    if epoch is None:
        epoch = read_fence(root, proc_id)["epoch"] + 1
    path = fence_path(root, proc_id)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump({"epoch": int(epoch), "topology": topology}, fh)
    os.replace(tmp, path)
    return int(epoch)


def _fname(session_id: str) -> str:
    # session ids are tenant-chosen strings: hash to a fixed-width safe
    # filename (the id itself rides in META)
    return hashlib.sha1(session_id.encode()).hexdigest()[:24] + _SUFFIX


def journal_session_id(path: str) -> Optional[str]:
    """Session id recorded in a journal's META frame (None when the
    file is torn/foreign) — what a dead process's orphaned journals are
    re-routed by (the filename is a hash; the id itself rides in META)."""
    try:
        for kind, payload in tfmt.read_frames(path):
            if kind == tfmt.KIND_META:
                meta = json.loads(payload)
                if meta.get("kind") == _META_KIND:
                    return meta.get("session_id")
                return None
            break  # META is always the first frame
    except Exception:
        return None
    return None


@contextmanager
def _frame_span(writer, kind: str):
    """One journal frame (encode + DEFLATE + write) as a ``ckpt.frame``
    span carrying the time the frame spent inside zlib."""
    before = writer.deflate_ms
    with _tracer.span("ckpt.frame", kind=kind) as span:
        yield
        if span is not None:
            span["attrs"]["deflate_ms"] = round(
                writer.deflate_ms - before, 3
            )


def _snapshot_request(p_cols: dict, r_cols: dict, kernel: str, top_k: int):
    """The session's padded columns as the wire's own message (what a
    SNAPSHOT frame holds)."""
    from protocol_tpu.proto import scheduler_pb2 as pb
    from protocol_tpu.proto import wire

    return pb.AssignRequestV2(
        providers=wire.encode_providers_v2(tfmt._as_ns(p_cols)),
        requirements=wire.encode_requirements_v2(tfmt._as_ns(r_cols)),
        kernel=kernel,
        top_k=top_k,
    )


def _pop_arena_meta(state: dict) -> dict:
    """Take the arena's scalars out of ``state`` (they ride the JSON
    meta, not the array pack) and return META's ``arena`` entry."""
    return {
        "warm_solves": state.pop("warm_solves"),
        "dual_age": state.pop("dual_age"),
        "weights_key": list(state.pop("weights_key")),
        # float-pipeline provenance (string scalar); restore_state cold
        # re-grounds on a mismatched-ISA load
        "native_isa": state.pop("native_isa", "scalar"),
    }


def _same(a, b) -> bool:
    """Arrays by identity, anything else (None, a scalar) by value."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return a is b
    return a == b


def _holds(now: dict, then: dict) -> bool:
    """Does ``now`` map the names of ``then`` to the same objects?"""
    return now.keys() == then.keys() and all(
        now[n] is a for n, a in then.items()
    )


class _PrefixJob:
    """The part of one tick's checkpoint that does not wait for its
    solve: the SNAPSHOT frame whole, and the ARENA payload's manifest
    and every buffer but the ``last`` ones fed into one DEFLATE stream
    (the tail's dtypes and shapes, which the manifest needs, are read
    off the arrays the tick before left). Made under the session lock
    from the arena's live state, run on the checkpointer's worker; it
    keeps the objects it read so the flush can tell whether they are
    still the session's."""

    def __init__(self, session, tick: int, live: dict, last: tuple,
                 parent: str):
        self.session_id = session.session_id
        self.tick = tick
        self.fingerprint = session.fingerprint
        self.kernel = session.kernel
        self.top_k = session.top_k
        self.p_cols = dict(session.p_cols)
        self.r_cols = dict(session.r_cols)
        self.live = dict(live)
        _pop_arena_meta(self.live)
        self.last = last
        self.parent = parent
        self.snapshot = tfmt.FrameDeflater(CKPT_COMPRESSLEVEL)
        self.arena = tfmt.FrameDeflater(CKPT_COMPRESSLEVEL)
        self.head = b""
        self.overlap_ms = 0.0
        # the job's wall on the worker, and of it the SNAPSHOT message
        # built and serialised (``encode_ms``): set when it has run
        self.took: dict = {}
        self.dropped = False
        self.future = None

    def run(self) -> None:
        with _tracer.stage(
            "ckpt.prefix", self.took, "worker_ms",
            remote_parent=self.parent or None, tick=self.tick,
        ) as span:
            with _tracer.stage("ckpt.encode", self.took, "encode_ms"):
                payload = tfmt.snapshot_payload(
                    self.session_id, self.fingerprint,
                    _snapshot_request(
                        self.p_cols, self.r_cols, self.kernel, self.top_k
                    ),
                )
            # both frames' full chunks go to the DEFLATE pool as they
            # are fed; this thread then takes the SNAPSHOT's last chunk
            # and the ARENA's open one while the pool runs the rest
            self.snapshot.feed(payload)
            self.head, arrays = tfmt.pack_plan(self.live, self.last)
            self.arena.feed(self.head)
            for name, a in arrays:
                if self.dropped:
                    return
                if name not in self.last:
                    self.arena.feed(tfmt.raw_bytes(a))
            self.snapshot.finish()
            self.arena.settle()
            self.overlap_ms = round(
                self.snapshot.take_ms() + self.arena.take_ms(), 3
            )
            if span is not None:
                span["attrs"].update(
                    bytes_raw=self.snapshot.bytes_raw + self.arena.bytes_raw,
                    deflate_ms=self.overlap_ms,
                )

    def fits_locked(self, session, live: dict, head: bytes) -> bool:
        """Was this prefix built for the tick ``session`` is at, from
        what it holds now: the same column and structure objects, and a
        manifest equal to ``head``, the one its exported state packs
        to?"""
        return (
            self.tick == int(session.tick)
            and head == self.head
            and _holds(session.p_cols, self.p_cols)
            and _holds(session.r_cols, self.r_cols)
            and all(
                n in self.last or _same(live.get(n), v)
                for n, v in self.live.items()
            )
        )


class SessionCheckpointer:
    """Per-session checkpoint writer/loader over ``<root>/<proc_id>/``
    (one namespace per servicer process; see the module docstring)."""

    def __init__(self, directory: str, every: int = 1,
                 proc_id: str = "p0"):
        self.root = directory
        self.proc_id = str(proc_id)
        self.directory = os.path.join(directory, self.proc_id)
        self.every = max(1, int(every))
        os.makedirs(self.directory, exist_ok=True)
        # fence adoption: cache the epoch the manager stamped before
        # spawning us (0 when unstamped — standalone layouts are inert).
        # A HIGHER epoch appearing on disk later means we were ejected.
        self.fence_epoch = read_fence(self.root, self.proc_id)["epoch"]
        self._fence_file = fence_path(self.root, self.proc_id)
        self._fence_cache: tuple = (None, {
            "epoch": self.fence_epoch, "topology": None,
        })
        # obs counters (scraped via the servicer's seam metrics)
        self.flushes = 0
        self.flush_failures = 0
        self.handoffs = 0
        self.fence_refusals = 0
        self.journals_skipped = 0
        # DEFLATE chunks of every journal flushed (one a frame whose
        # payload fits ``tfmt.DEFLATE_CHUNK``)
        self.chunks = 0
        # what the last successful flush cost (flush_ms, export_ms,
        # deflate_ms, bytes_raw, bytes_out = the journal's size on
        # disk, chunks = its frames' DEFLATE chunks; prefix = hit /
        # miss / stale / error, join_ms = its wait for the worker,
        # overlap_ms = the zlib time a hit took off the
        # flush; worker_ms / encode_ms = the wall of the job it found
        # run, hit or stale, and of its SNAPSHOT message); the
        # servicer, which owns the seam, records it
        self.last_flush: dict = {}
        # prefix jobs: at most one a session, all on one worker thread
        # (started with the first job). No lock: every access is one
        # dict operation, and a session's jobs are made and taken under
        # that session's lock
        self._jobs: dict = {}
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ckpt-prefix"
        )

    def path_for(self, session_id: str) -> str:
        return os.path.join(self.directory, _fname(session_id))

    def peer_path(self, session_id: str, proc_id: str) -> str:
        """Where ``session_id``'s journal lives in ANOTHER process's
        namespace under the same root (the handoff target)."""
        return os.path.join(self.root, str(proc_id), _fname(session_id))

    # ---------------- fencing ----------------

    def fence_state(self) -> dict:
        """The namespace's CURRENT on-disk fence stamp, stat-cached (a
        check costs one ``os.stat`` unless the file changed). Benign
        under concurrency: the cache tuple swaps atomically and the
        worst case is one redundant re-read."""
        try:
            st = os.stat(self._fence_file)
            sig: Optional[tuple] = (st.st_mtime_ns, st.st_size)
        except OSError:
            sig = None
        cached_sig, cached = self._fence_cache
        if sig != cached_sig:
            cached = read_fence(self.root, self.proc_id)
            self._fence_cache = (sig, cached)
        return cached

    def fence_superseded(self) -> bool:
        """True when a HIGHER fence epoch was stamped into this
        namespace than the one this process adopted at boot: we were
        ejected (detector, orphan handoff) and must neither flush nor
        ack — the journals belong to the ring's survivors now."""
        return self.fence_state()["epoch"] > self.fence_epoch

    def due(self, tick: int) -> bool:
        """Is ``tick`` on the flush cadence? Tick 0 (the snapshot
        solve) always checkpoints — a crash before the first delta must
        still restore the session."""
        return tick == 0 or tick % self.every == 0

    # ---------------- prefix (overlap with the solve) ----------------

    def arm_locked(self, session) -> None:
        """Before a tick's solve (caller holds ``session.lock``): if
        the tick about to be acknowledged is ``due`` and the arena can
        say when its structure is final, have it start that tick's
        prefix job then. An arena without the hook (native) is left
        alone, a tick off the cadence gets none; the flush disarms."""
        arena = session.arena
        if not hasattr(arena, "structure_hook"):
            return
        tick = int(session.tick) + 1
        if not self.due(tick):
            arena.structure_hook = None
            return

        def start(live: dict) -> None:
            # called inside the arena's solve: whatever goes wrong here
            # costs the overlap, never the tick
            try:
                job = _PrefixJob(
                    session, tick, live, tuple(arena.SOLVE_STATE),
                    _tracer.header(),
                )
                self.forget(job.session_id)
                self._jobs[job.session_id] = job
                job.future = self._pool.submit(job.run)
            except Exception:
                log.warning(
                    "checkpoint prefix not started for %s",
                    session.session_id, exc_info=True,
                )

        arena.structure_hook = start

    def forget(self, session_id: str) -> None:
        """Drop the session's prefix job, if any (the session was let
        go, or a newer job takes its place): it stops at its next
        buffer and nobody reads it."""
        job = self._jobs.pop(session_id, None)
        if job is not None:
            job.dropped = True
            if job.future is not None:
                job.future.cancel()

    def _join_prefix_locked(self, session, state, last: tuple, took: dict):
        """The session's prefix job if this flush can use it, else
        None. ``took["prefix"]`` says which: ``hit``; ``miss`` (no job,
        or it had not begun to run: sessions share the one worker);
        ``stale`` (built for another tick, or from objects the session
        no longer holds); ``error`` (it raised). ``took["join_ms"]`` is
        the wait for a running job; a job that ran to its end, hit or
        stale, also gives its ``worker_ms`` and ``encode_ms``."""
        took.update(prefix="miss", join_ms=0.0, overlap_ms=0.0)
        if hasattr(session.arena, "structure_hook"):
            session.arena.structure_hook = None
        job = self._jobs.pop(session.session_id, None)
        if job is None or state is None or job.future.cancel():
            return None
        t0 = time.perf_counter()
        try:
            job.future.result()
        except Exception:
            took["prefix"] = "error"
            log.warning(
                "checkpoint prefix failed for %s; written without it",
                session.session_id, exc_info=True,
            )
            return None
        finally:
            took["join_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        took.update(job.took)
        head, _arrays = tfmt.pack_plan(state, last)
        if not job.fits_locked(session, session.arena.live_state(), head):
            took["prefix"] = "stale"
            return None
        took.update(prefix="hit", overlap_ms=job.overlap_ms)
        return job

    # ---------------- write ----------------

    def flush_locked(self, session) -> bool:
        """Write the session's checkpoint (caller holds
        ``session.lock`` — the state must be a consistent tick). Best
        effort: a failed flush warns and counts, never fails the RPC;
        the cost is one potential reopen after a crash.

        A SUPERSEDED FENCE refuses outright (counted separately): an
        ejected process writing into a namespace whose journals were
        re-routed would resurrect state a survivor already owns — the
        exact split-brain the fence exists to make impossible."""
        if self.fence_superseded():
            self.fence_refusals += 1
            return False
        took: dict = {}
        try:
            with _tracer.stage("ckpt.flush", took, "flush_ms") as span:
                self._write_locked(session, took)
                if span is not None:
                    span["attrs"].update(
                        bytes_raw=took["bytes_raw"],
                        bytes_out=took["bytes_out"],
                        prefix=took["prefix"],
                        join_ms=took["join_ms"],
                    )
            self.flushes += 1
            self.chunks += took["chunks"]
            self.last_flush = took
            return True
        except Exception:
            self.flush_failures += 1
            log.warning(
                "session checkpoint flush failed for %s",
                session.session_id, exc_info=True,
            )
            return False

    def _write_locked(self, session, took: dict) -> None:
        with _tracer.stage("ckpt.export", took, "export_ms"):
            state = session.arena.export_state()
        meta = {
            "kind": _META_KIND,
            "session_id": session.session_id,
            "fingerprint": session.fingerprint,
            "kernel": session.kernel,
            "threads": int(session.threads),
            "top_k": int(session.top_k),
            "weights": [
                float(session.weights.price),
                float(session.weights.load),
                float(session.weights.proximity),
                float(session.weights.priority),
            ],
            "n_providers": int(session.n_providers),
            "n_tasks": int(session.n_tasks),
            "tick": int(session.tick),
            "last_delta_crc": int(session.last_delta_crc),
            "delta_rows_total": int(session.delta_rows_total),
        }
        if session.stream is not None:
            # the FULL stream state travels with the journal (ISSUE
            # 20): config + per-source dedup cursors + the reconcile-
            # cadence cursor + obs counters. The wire tick/CRC cursor
            # only dedups a resend of the LAST tick — a chaos'd
            # retransmit arriving as a FRESH tick after a migration
            # handoff would double-apply without the seq cursors at
            # the target. The gap tracker / divergence baseline are
            # rebased exactly from the restored arena at re-arm.
            meta["stream"] = session.stream.export_state()
        if state is not None:
            meta["arena"] = _pop_arena_meta(state)
        # what the arena's solve writes lies last in the ARENA payload,
        # prefix or none: one layout, so one journal for one state
        last = tuple(getattr(session.arena, "SOLVE_STATE", ()))
        job = self._join_prefix_locked(session, state, last, took)
        request = snapshot = arena = None
        if job is not None:
            snapshot, arena = job.snapshot, job.arena
        final = self.path_for(session.session_id)
        tmp = final + ".tmp"
        writer = tfmt.TraceWriter(
            tmp, meta=meta, compresslevel=CKPT_COMPRESSLEVEL
        )
        try:
            with _frame_span(writer, "snapshot"):
                if job is None:
                    request = _snapshot_request(
                        session.p_cols, session.r_cols, session.kernel,
                        session.top_k,
                    )
                writer.write_snapshot(
                    session.session_id, session.fingerprint, request,
                    deflated=snapshot,
                )
            if state is not None:
                with _frame_span(writer, "arena"):
                    writer.write_arena(state, last, deflated=arena)
            if session.last_p4t is not None:
                with _frame_span(writer, "outcome"):
                    writer.write_outcome(
                        int(session.tick),
                        np.asarray(session.last_p4t, np.int32),
                    )
        finally:
            writer.close()
        os.replace(tmp, final)
        took.update(
            deflate_ms=round(writer.deflate_ms, 3),
            bytes_raw=writer.bytes_raw, bytes_out=writer.bytes_out,
            chunks=writer.deflate_chunks,
        )

    # ---------------- migration handoff ----------------

    def handoff(self, session_id: str, dst_proc_id: str) -> bool:
        """Atomically move ``session_id``'s journal from this process's
        namespace into ``dst_proc_id``'s (``os.replace`` — same
        filesystem, so the journal exists in exactly one namespace at
        every instant: two processes can never BOTH rehydrate it).
        False = no journal to move (never flushed, or already handed
        off)."""
        src = self.path_for(session_id)
        dst = self.peer_path(session_id, dst_proc_id)
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        try:
            os.replace(src, dst)
        except OSError:
            return False
        self.handoffs += 1
        return True

    # ---------------- read ----------------

    def load_all(self, budget=None, limit: Optional[int] = None) -> list:
        """Rehydrate the loadable checkpoints in the directory into
        fresh :class:`SolveSession` objects (sorted by session id for a
        deterministic restore order). ``limit`` caps the restore at the
        N most-recently-flushed files (the caller's session budget —
        restoring more would make the store's LRU pressure evict the
        sessions just restored). Unloadable files are skipped with a
        warning — the affected client re-opens down the ladder."""
        out = []
        try:
            names = sorted(
                n for n in os.listdir(self.directory)
                if n.endswith(_SUFFIX)
            )
        except OSError:
            return out
        if limit is not None and len(names) > limit:
            def _mtime(name: str) -> float:
                try:
                    return os.path.getmtime(
                        os.path.join(self.directory, name)
                    )
                except OSError:
                    return 0.0

            skipped = len(names) - limit
            names = sorted(
                sorted(names, key=_mtime)[-limit:]
            )
            log.warning(
                "checkpoint restore capped at %d sessions "
                "(%d older files skipped)", limit, skipped,
            )
        loaded = []
        for name in names:
            path = os.path.join(self.directory, name)
            try:
                loaded.append(self._load(path, budget))
            except Exception:
                # torn META/frames (killed mid-flush), version drift,
                # decode error: COUNTED skip, never a failed restore —
                # the affected client re-opens down the ladder
                self.journals_skipped += 1
                log.warning(
                    "skipping unloadable session checkpoint %s", path,
                    exc_info=True,
                )
        loaded.sort(key=lambda s: s.session_id)
        out.extend(loaded)
        return out

    def _load(self, path: str, budget):
        from protocol_tpu.fleet import estimate_arena_bytes
        from protocol_tpu.ops.cost import CostWeights
        from protocol_tpu.services.session_store import (
            SolveSession,
            make_solve_arena,
            parse_session_kernel,
        )

        meta: Optional[dict] = None
        snapshot = None
        arena_state: Optional[dict] = None
        outcome = None
        for kind, payload in tfmt.read_frames(path):
            if kind == -1:
                raise ValueError(f"{path}: torn checkpoint tail")
            if kind == tfmt.KIND_META:
                meta = json.loads(payload)
            elif kind == tfmt.KIND_SNAPSHOT:
                snapshot = tfmt._parse_snapshot(payload)
            elif kind == tfmt.KIND_ARENA:
                arena_state = tfmt.unpack_arrays(payload)
            elif kind == tfmt.KIND_OUTCOME:
                outcome = tfmt._parse_outcome(payload)
        if meta is None or meta.get("kind") != _META_KIND:
            raise ValueError(f"{path}: not a session checkpoint")
        if snapshot is None:
            raise ValueError(f"{path}: checkpoint has no snapshot frame")
        parsed = parse_session_kernel(meta["kernel"])
        if parsed is None:
            raise ValueError(
                f"{path}: checkpointed kernel {meta['kernel']!r} is not "
                "session-servable"
            )
        engine, _ = parsed
        threads = int(meta["threads"])
        arena = make_solve_arena(
            engine, k=int(meta["top_k"]), threads=threads
        )
        p_cols, r_cols = snapshot.p_cols, snapshot.r_cols  # lint: unlocked-ok (parsed trace frame, not a live session)
        if arena_state is not None:
            am = meta.get("arena") or {}
            arena_state["warm_solves"] = int(am.get("warm_solves", 0))
            arena_state["dual_age"] = int(am.get("dual_age", 0))
            arena_state["weights_key"] = tuple(
                am.get("weights_key") or meta["weights"]
            )
            arena_state["native_isa"] = str(am.get("native_isa", "scalar"))
            arena.restore_state(
                tfmt._as_ns(p_cols), tfmt._as_ns(r_cols), arena_state
            )
        session = SolveSession(
            session_id=meta["session_id"],
            fingerprint=meta["fingerprint"],
            weights=CostWeights(*meta["weights"]),
            kernel=meta["kernel"],
            threads=threads,
            top_k=int(meta["top_k"]),
            p_cols=p_cols,
            r_cols=r_cols,
            n_providers=int(meta["n_providers"]),
            n_tasks=int(meta["n_tasks"]),
            arena=arena,
            tick=int(meta["tick"]),
            budget=budget,
            arena_bytes=estimate_arena_bytes(
                p_cols, r_cols, int(meta["top_k"])
            ),
        )
        stream_meta = meta.get("stream")
        if stream_meta and arena._p4t is not None:
            # re-arm the stream engine over the restored warm arena
            # with the FULL exported state (dedup cursors, cadence
            # cursor, counters — see StreamEngine.from_state); a carry
            # that degraded to cold (no arena state) stays a batch
            # session — the client's ladder re-opens with stream_mode,
            # an honest degrade rather than an unprimed engine
            from protocol_tpu.stream.engine import StreamEngine

            session.stream = StreamEngine.from_state(
                arena, CostWeights(*meta["weights"]), stream_meta
            )
        # fresh object, not yet visible to any store: no lock exists yet
        session.delta_rows_total = int(meta.get("delta_rows_total", 0))  # lint: unlocked-ok (fresh object)
        session.last_delta_crc = int(meta.get("last_delta_crc", 0))  # lint: unlocked-ok (fresh object)
        if outcome is not None:
            session.last_p4t = np.asarray(  # lint: unlocked-ok (fresh object)
                outcome.provider_for_task, np.int32
            )
        return session

    def load_one(self, session_id: str, budget=None):
        """Rehydrate ONE session from this process's namespace (the
        lazy-restore path behind a delta miss after a migration
        handoff). None = no journal here, or unloadable (warned — the
        client falls down the ladder). The ownership re-check after the
        read closes the rename race: a journal handed off mid-read is
        discarded, never served."""
        path = self.path_for(session_id)
        if not os.path.exists(path):
            return None
        try:
            session = self._load(path, budget)
        except Exception:
            self.journals_skipped += 1
            log.warning(
                "skipping unloadable session checkpoint %s", path,
                exc_info=True,
            )
            return None
        if session.session_id != session_id:
            # hash-prefix collision between two session ids: refuse
            # rather than serve someone else's state
            return None
        if not os.path.exists(path):
            # handed off to another namespace while we were reading:
            # the target owns it now
            return None
        return session

    def drop(self, session_id: str) -> None:
        """Remove a session's checkpoint (explicit client drop — an
        evicted-for-pressure session keeps its file: resurrecting it on
        restart is harmless, a same-id reopen just overwrites)."""
        try:
            os.remove(self.path_for(session_id))
        except OSError:
            pass


def handoff_orphans(
    root: str,
    src_proc_id: str,
    route,
    topology: Optional[dict] = None,
    stats: Optional[dict] = None,
) -> list:
    """Re-route a DEAD (or ejected) process's journal namespace: every
    loadable journal under ``<root>/<src_proc_id>/`` is renamed into
    the namespace ``route(session_id)`` picks (None = leave in place).
    Returns ``[(session_id, dst_proc_id), ...]`` for the journals
    moved. The source namespace's FENCE is superseded FIRST (stamped
    with ``topology``, the post-ejection ring): a paused-not-dead
    source that resumes mid- or post-handoff finds its fence
    superseded and refuses to flush or ack — re-routing is safe even
    when "dead" was really "wedged". A journal whose META frame is
    torn (process killed mid-flush) is SKIPPED with a counted
    ``journals_skipped`` warning instead of raising out of the
    re-route loop — the affected client re-opens down the ladder, the
    remaining journals still move. ``stats`` (optional dict) receives
    ``journals_moved`` / ``journals_skipped`` / ``fence_epoch``."""
    src_dir = os.path.join(root, str(src_proc_id))
    moved = []
    if stats is None:
        stats = {}
    stats.setdefault("journals_moved", 0)
    stats.setdefault("journals_skipped", 0)
    # fence FIRST, then enumerate: a wedged-but-running source that
    # flushes between the listing and the stamp would land a journal
    # that is neither moved nor fence-refused — stamping before the
    # listdir means any flush that beats the stamp is IN the listing,
    # and any flush after it is refused by the fence
    stats["fence_epoch"] = stamp_fence(
        root, src_proc_id, topology=topology
    )
    try:
        names = sorted(
            n for n in os.listdir(src_dir) if n.endswith(_SUFFIX)
        )
    except OSError:
        return moved
    for name in names:
        path = os.path.join(src_dir, name)
        sid = journal_session_id(path)
        if sid is None:
            stats["journals_skipped"] += 1
            log.warning(
                "orphan journal %s has no readable META "
                "(torn mid-flush?) — skipped, not fatal", path,
            )
            continue
        dst_proc = route(sid)
        if dst_proc is None or str(dst_proc) == str(src_proc_id):
            continue
        dst_dir = os.path.join(root, str(dst_proc))
        os.makedirs(dst_dir, exist_ok=True)
        try:
            os.replace(path, os.path.join(dst_dir, name))
        except OSError:
            stats["journals_skipped"] += 1
            log.warning("orphan handoff failed for %s", path,
                        exc_info=True)
            continue
        moved.append((sid, str(dst_proc)))
    stats["journals_moved"] = len(moved)
    return moved
