"""The checkpoint prefix (ISSUE 27): on a warm jax tick the
solve-independent part of the journal is DEFLATEd on the checkpointer's
worker while the solve runs, and the flush writes byte for byte what
the sequential path writes for the same session state. Whatever the
flush cannot use (no solve before it, a native arena, a stale job, a
job that raised) is written by the sequential path, whole and loadable,
and counted. CPU, 256 rows, through the loopback servicer of
``test_span_tree.py``."""

import os
import struct
import sys
import threading
import zlib

import numpy as np
import pytest

pytest.importorskip("jax")
pytest.importorskip("grpc")

from protocol_tpu import native  # noqa: E402
from protocol_tpu.faults import checkpoint as ckpt_mod  # noqa: E402
from protocol_tpu.faults.checkpoint import SessionCheckpointer  # noqa: E402
from protocol_tpu.obs.spans import TRACER  # noqa: E402
from protocol_tpu.trace import format as tfmt  # noqa: E402
from tests.test_span_tree import ROWS, _Served, time_limit  # noqa: E402,F401

NATIVE = native.available()


def _session(served):
    session, _why = served.server.servicer.sessions.get(served.sid, served.fp)
    return session


def _journal(ckpt: SessionCheckpointer, sid: str) -> bytes:
    with open(ckpt.path_for(sid), "rb") as fh:
        return fh.read()


def _sequential(session, directory) -> bytes:
    """The journal a checkpointer that never saw the tick (so has no
    prefix job) writes for the session's state as it stands."""
    ref = SessionCheckpointer(str(directory))
    with session.lock:
        assert ref.flush_locked(session)
    assert ref.last_flush["prefix"] == "miss"
    return _journal(ref, session.session_id)


def _loads(journal: bytes, sid: str, directory):
    """``load_one`` of ``journal`` from a namespace of its own."""
    loader = SessionCheckpointer(str(directory))
    with open(loader.path_for(sid), "wb") as fh:
        fh.write(journal)
    loaded = loader.load_one(sid)
    assert loaded is not None and loader.journals_skipped == 0
    return loaded


def _kinds(journal: bytes, tmp_path) -> list:
    path = os.path.join(str(tmp_path), "walk.ckpt")
    with open(path, "wb") as fh:
        fh.write(journal)
    return [kind for kind, _ in tfmt.read_frames(path)]


def _raw_frames(data: bytes) -> list:
    """``(kind, flags, body)`` of every frame of a file's bytes, the
    bodies as they lie on disk."""
    at, out = len(tfmt.MAGIC), []
    assert data[:at] == tfmt.MAGIC
    while at < len(data):
        kind, flags, length, crc = tfmt._HEADER.unpack_from(data, at)
        at += tfmt._HEADER.size
        body = data[at:at + length]
        assert len(body) == length and zlib.crc32(body) == crc
        out.append((kind, flags, body))
        at += length
    return out


def _payloads_at(data: bytes, level: int, deflate=zlib.compress) -> list:
    """``(kind, payload)`` of every frame, each DEFLATEd body being
    what ``deflate`` gives for its payload at ``level``."""
    out = []
    for kind, flags, body in _raw_frames(data):
        payload = body
        if flags & tfmt._FLAG_DEFLATE:
            payload = zlib.decompress(body)
            assert body == deflate(payload, level), (kind, level)
        out.append((kind, payload))
    return out


WHOLE = [tfmt.KIND_META, tfmt.KIND_SNAPSHOT, tfmt.KIND_ARENA,
         tfmt.KIND_OUTCOME]
# the two levels a PTTRACE1 file is written at (ISSUE 34), by who
# writes it
LEVELS = {
    "trace": tfmt.COMPRESSLEVEL,
    "checkpoint": ckpt_mod.CKPT_COMPRESSLEVEL,
}


CHUNK = tfmt.DEFLATE_CHUNK
# a session whose ARENA payload (~1.5 MB) is more than one chunk
BIG_ROWS = 1024


def _piecewise(payload: bytes, level: int) -> bytes:
    """The zlib stream a frame's payload DEFLATEs to, built here from
    zlib alone: ``zlib.compress`` for one chunk or less, else the
    header, every ``CHUNK`` bytes as raw DEFLATE ended by a sync flush
    (the last by ``Z_FINISH``), and the payload's Adler-32."""
    if len(payload) <= CHUNK:
        return zlib.compress(payload, level)
    out = [zlib.compress(b"", level)[:2]]
    for at in range(0, len(payload), CHUNK):
        z = zlib.compressobj(level, zlib.DEFLATED, -zlib.MAX_WBITS)
        last = at + CHUNK >= len(payload)
        out.append(z.compress(payload[at:at + CHUNK]))
        out.append(z.flush(zlib.Z_FINISH if last else zlib.Z_SYNC_FLUSH))
    out.append(struct.pack(">I", zlib.adler32(payload)))
    return b"".join(out)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = _Served(str(tmp_path_factory.mktemp("ckpt")))
    try:
        yield s
    finally:
        s.close()


@pytest.fixture(scope="module")
def big(tmp_path_factory):
    s = _Served(str(tmp_path_factory.mktemp("big")), rows=BIG_ROWS)
    try:
        yield s
    finally:
        s.close()


def test_every_acks_journal_is_the_sequential_one_and_continues_the_chain(
    served, tmp_path
):
    _every_ack_is_the_sequential_journal(served, tmp_path)


def test_every_acks_journal_in_chunks_is_the_sequential_one(big, tmp_path):
    """The same at a payload above one chunk: the worker's feeding of
    the ARENA frame ends inside a chunk the flush then continues, and
    the journal is still the sequential path's, byte for byte."""
    journals = _every_ack_is_the_sequential_journal(big, tmp_path)
    ckpt = big.server.servicer.ckpt
    # META, SNAPSHOT and OUTCOME one chunk each, ARENA more than one
    assert ckpt.last_flush["chunks"] > len(WHOLE)
    arena = _raw_frames(journals[-1])[2]
    assert arena[0] == tfmt.KIND_ARENA and arena[1] & tfmt._FLAG_DEFLATE
    payload = zlib.decompress(arena[2])
    assert len(payload) > CHUNK
    assert arena[2] == _piecewise(payload, LEVELS["checkpoint"])
    assert arena[2] != zlib.compress(payload, LEVELS["checkpoint"])
    # six flushes: the open's and five acks'
    assert big.seam()["ckpt_chunks_sum"] == ckpt.chunks
    assert ckpt.chunks > 6 * len(WHOLE)


def _every_ack_is_the_sequential_journal(served, tmp_path) -> list:
    """Five warm acks of a fresh servicer: each one's journal is the
    one a checkpointer with no prefix writes for the same state, and
    the second, loaded, continues the chain bit for bit. Returns the
    journals."""
    ckpt = served.server.servicer.ckpt
    session = _session(served)
    # the cold open's flush: no warm structure to start from
    assert ckpt.last_flush["prefix"] == "miss"
    assert _journal(ckpt, served.sid) == _sequential(
        session, tmp_path / "ref0"
    )
    journals, deltas, plans = [], [], []
    for n in range(1, 6):
        mark = TRACER.mark()
        trace = served.tick()
        journal = _journal(ckpt, served.sid)
        assert journal == _sequential(session, tmp_path / f"ref{n}"), n
        assert _kinds(journal, tmp_path) == WHOLE
        assert ckpt.last_flush["prefix"] == "hit", n
        assert ckpt.last_flush["overlap_ms"] > ckpt.last_flush["deflate_ms"]
        spans = {
            s["name"]: s for s in TRACER.since(mark, trace=trace)
        }
        assert spans["ckpt.flush"]["attrs"]["prefix"] == "hit"
        assert spans["ckpt.prefix"]["attrs"]["tick"] == n
        journals.append(journal)
        deltas.append((
            served.rows,
            {k: v[served.rows] for k, v in served.p_cols.items()},
        ))
        plans.append(served.plan.copy())
    assert ckpt.flush_failures == 0
    seam = served.seam()
    assert seam["session_ckpt_prefix_hit"] == 5
    assert seam["session_ckpt_prefix_miss"] == 1
    assert seam["ckpt_overlap_count"] == 5
    # the worker's wall and its SNAPSHOT message: one reading a hit
    assert seam["ckpt_worker_count"] == seam["ckpt_encode_count"] == 5
    assert seam["ckpt_worker_ms_sum"] > seam["ckpt_encode_ms_sum"] > 0
    # the journal of tick 2, loaded, continues the chain bit for bit
    loaded = _loads(journals[1], served.sid, tmp_path / "load")
    assert loaded.tick == 2
    np.testing.assert_array_equal(loaded.last_p4t, plans[1])
    none = np.zeros(0, np.int32)
    for n in (3, 4, 5):
        rows, p_delta = deltas[n - 1]
        loaded.apply_delta(rows, p_delta, none, {})
        p4t, _t4p, _price = loaded.solve()
        np.testing.assert_array_equal(p4t, plans[n - 1])
    assert loaded.arena.last_stats["cold"] is False
    return journals


def test_a_journal_in_chunks_restores_and_continues_the_chain(
    big, tmp_path
):
    """A journal whose frames were DEFLATEd in chunks reads through
    ``read_frames`` and restores through ``load_one``, and so does the
    one a writer of one zlib stream a frame, as before chunks,
    wrote for the same state: both serve the chain's next ticks to the
    served plans, and their payloads are the same frame for frame."""
    session = _session(big)
    ours = _journal(big.server.servicer.ckpt, big.sid)
    payloads = _payloads_at(ours, LEVELS["checkpoint"], _piecewise)
    assert [kind for kind, _ in payloads] == WHOLE
    assert _kinds(ours, tmp_path) == WHOLE
    # the same frames, each body one ``zlib.compress`` stream
    theirs = bytearray(tfmt.MAGIC)
    for kind, flags, body in _raw_frames(ours):
        if flags & tfmt._FLAG_DEFLATE:
            body = zlib.compress(zlib.decompress(body), LEVELS["checkpoint"])
        theirs += tfmt._HEADER.pack(kind, flags, len(body), zlib.crc32(body))
        theirs += body
    theirs = bytes(theirs)
    assert theirs != ours
    assert _payloads_at(theirs, LEVELS["checkpoint"]) == payloads
    loaded = [
        _loads(journal, big.sid, tmp_path / f"load-{name}")
        for name, journal in (("ours", ours), ("theirs", theirs))
    ]
    none = np.zeros(0, np.int32)
    for _ in range(2):
        big.tick()
        rows = big.rows
        p_delta = {k: v[rows] for k, v in big.p_cols.items()}
        for restored in loaded:
            restored.apply_delta(rows, p_delta, none, {})
            p4t, _t4p, _price = restored.solve()
            assert restored.arena.last_stats["cold"] is False
            np.testing.assert_array_equal(p4t, big.plan)
    assert loaded[0].tick == loaded[1].tick == session.tick - 2


def _miss(served, before: dict, tmp_path, why: str) -> None:
    """The last flush fell back: counted, and the journal is the
    sequential one, whole and loadable."""
    servicer = served.server.servicer
    session = _session(served)
    assert servicer.ckpt.last_flush["prefix"] == why
    assert servicer.ckpt.last_flush["overlap_ms"] == 0.0
    # a job that ran to its end gives its wall, stale or not; a flush
    # that found none run makes none up
    assert ("worker_ms" in servicer.ckpt.last_flush) == (why == "stale")
    journal = _journal(servicer.ckpt, served.sid)
    assert journal == _sequential(session, tmp_path / "ref")
    assert _kinds(journal, tmp_path) == WHOLE
    loaded = _loads(journal, served.sid, tmp_path / "load")
    assert loaded.tick == session.tick
    np.testing.assert_array_equal(loaded.last_p4t, served.plan)
    after = served.seam()
    assert after["session_ckpt_prefix_miss"] == (
        before["session_ckpt_prefix_miss"] + 1
    )
    assert after.get("session_ckpt_prefix_hit", 0) == before.get(
        "session_ckpt_prefix_hit", 0
    )
    assert after["ckpt_flush_failures"] == 0


class TestFallbacks:
    def test_a_flush_with_no_solve_before_it(self, served, tmp_path):
        # eviction, drain and the handoff flush a session as it stands
        served.tick()
        before = served.seam()
        servicer = served.server.servicer
        session = _session(served)
        with session.lock:
            assert servicer._flush_locked(session)
        _miss(served, before, tmp_path, "miss")

    def test_a_job_whose_arrays_were_replaced(
        self, served, tmp_path, monkeypatch
    ):
        ckpt = served.server.servicer.ckpt
        arm = ckpt.arm_locked

        def arm_then_replace(session):
            arm(session)
            arena, start = session.arena, session.arena.structure_hook

            def hook(live):
                start(live)
                # equal values, other objects: the solve is the same,
                # the prefix no longer the arena's
                arena._cand_p = arena._cand_p.copy()

            arena.structure_hook = hook

        monkeypatch.setattr(ckpt, "arm_locked", arm_then_replace)
        before = served.seam()
        served.tick()
        _miss(served, before, tmp_path, "stale")

    def test_a_job_that_raises(self, served, tmp_path, monkeypatch):
        def boom(self):
            raise RuntimeError("planted in the prefix job")

        monkeypatch.setattr(ckpt_mod._PrefixJob, "run", boom)
        before = served.seam()
        served.tick()
        _miss(served, before, tmp_path, "error")

    def test_the_tick_after_a_fallback_hits_again(self, served):
        served.tick()
        assert served.server.servicer.ckpt.last_flush["prefix"] == "hit"

    def test_a_tick_off_the_cadence_starts_no_job(self, served, monkeypatch):
        ckpt = served.server.servicer.ckpt
        monkeypatch.setattr(ckpt, "every", 1000)
        flushes = ckpt.flushes
        served.tick()
        assert ckpt.flushes == flushes and not ckpt._jobs
        assert _session(served).arena.structure_hook is None

    @pytest.mark.skipif(not NATIVE, reason="no native toolchain")
    def test_a_native_arena_session(self, tmp_path):
        s = _Served(str(tmp_path / "ckpt"), kernel="native-mt")
        try:
            before = s.seam()
            assert before["session_ckpt_prefix_miss"] == 1  # the open
            s.tick()
            assert not hasattr(_session(s).arena, "structure_hook")
            _miss(s, before, tmp_path, "miss")
        finally:
            s.close()

    def test_the_handoff_moves_a_whole_journal(self, tmp_path):
        s = _Served(str(tmp_path / "ckpt"))
        try:
            s.tick()
            servicer = s.server.servicer
            before = s.seam()
            assert servicer.migrate_out("127.0.0.1:1", "p1") == 1
            assert servicer.ckpt.last_flush["prefix"] == "miss"
            after = s.seam()
            assert after["session_ckpt_prefix_miss"] == (
                before["session_ckpt_prefix_miss"] + 1
            )
            assert after["ckpt_flush_failures"] == 0
            moved = servicer.ckpt.peer_path(s.sid, "p1")
            with open(moved, "rb") as fh:
                journal = fh.read()
            loaded = _loads(journal, s.sid, tmp_path / "load")
            assert loaded.tick == 1
            np.testing.assert_array_equal(loaded.last_p4t, s.plan)
        finally:
            s.close()


@pytest.mark.parametrize("level", LEVELS.values(), ids=LEVELS.keys())
def test_a_stream_fed_in_pieces_is_the_stream_of_one_call(level):
    rng = np.random.default_rng(27)
    named = {
        "cand_p": rng.integers(0, ROWS, (ROWS, 80)).astype(np.int32),
        "cand_c": rng.random((ROWS, 80)).astype(np.float32),
        "none": None,
        "empty": np.zeros((0, 4), np.int32),
        "price": rng.random(ROWS).astype(np.float32),
        "retired": rng.random(ROWS) > 0.5,
    }
    last = ("price", "retired")
    whole = tfmt.pack_arrays(named, last)
    head, arrays = tfmt.pack_plan(named, last)
    d = tfmt.FrameDeflater(level)
    d.feed(head)
    for _name, a in arrays:
        d.feed(tfmt.raw_bytes(a))
    assert d.bytes_raw == len(whole) <= CHUNK
    assert d.finish() == (1, zlib.compress(whole, level))
    assert d.finish() is d.finish()
    assert d.chunks == 1
    assert d.take_ms() > 0 and d.take_ms() == 0
    # a payload DEFLATE cannot shorten is stored as it is, as _frame does
    noise = rng.bytes(4096)
    d = tfmt.FrameDeflater(level)
    d.feed(noise[:100])
    d.feed(noise[100:])
    assert d.finish() == (0, noise)


LENGTHS = {
    "empty": 0, "one-byte": 1, "chunk-less-1": CHUNK - 1, "chunk": CHUNK,
    "chunk-plus-1": CHUNK + 1, "5.5-chunks": 5 * CHUNK + CHUNK // 2,
}
# where a payload's feeds end, by its length
CUTS = {
    "one-call": lambda n: [],
    # every feed ends on a seam between two chunks
    "at-seams": lambda n: list(range(CHUNK, n, CHUNK)),
    # every seam falls inside a feed of eight bytes
    "straddling": lambda n: [
        x for s in range(CHUNK, n + 1, CHUNK) for x in (s - 3, s + 5)
        if 0 < x < n
    ],
    # a one-byte feed first, then feeds of a third of a chunk or so
    "ragged": lambda n: list(range(1, n, 333_331)),
}


@pytest.fixture(scope="module")
def payloads():
    """Bytes that DEFLATE well, as a journal's int32 columns do."""
    rng = np.random.default_rng(42)
    n = max(LENGTHS.values())
    return rng.integers(0, 300, n // 4, dtype=np.int32).tobytes()


@pytest.mark.parametrize("level", LEVELS.values(), ids=LEVELS.keys())
@pytest.mark.parametrize("cuts", CUTS.values(), ids=CUTS.keys())
@pytest.mark.parametrize("n", LENGTHS.values(), ids=LENGTHS.keys())
def test_a_payload_fed_in_any_cuts_is_its_piecewise_stream(
    n, cuts, level, payloads
):
    """Whatever the feeds, a frame's body is the piecewise stream of the
    joined payload, which any ``zlib.decompress`` reads; at one chunk or
    less it is ``zlib.compress``'s, so small frames keep their bytes."""
    payload = payloads[:n]
    d, at = tfmt.FrameDeflater(level), 0
    for cut in [*cuts(n), n]:
        d.feed(memoryview(payload)[at:cut])
        at = cut
    assert d.bytes_raw == n
    flags, body = d.finish()
    stream = _piecewise(payload, level)
    assert zlib.decompress(stream) == payload
    if len(stream) < n:
        assert (flags, body) == (tfmt._FLAG_DEFLATE, stream)
        assert zlib.decompress(body) == payload
    else:  # stored as it is, as a frame always was
        assert (flags, body) == (0, payload)
    assert d.chunks == max(1, -(-n // CHUNK))
    if n <= CHUNK:
        assert stream == zlib.compress(payload, level)
    else:
        assert stream != zlib.compress(payload, level)
    assert d.take_ms() > 0


@pytest.mark.parametrize("n", [CHUNK + 1, 5 * CHUNK + CHUNK // 2])
def test_the_trailer_is_the_payloads_adler32_from_its_chunks(n, payloads):
    payload = payloads[:n]
    d = tfmt.FrameDeflater(LEVELS["checkpoint"])
    d.feed(payload)
    flags, body = d.finish()
    assert flags == tfmt._FLAG_DEFLATE and d.chunks > 1
    assert body[-4:] == struct.pack(">I", zlib.adler32(payload))
    # and the combination itself, at every chunk's seam and off them
    for cut in (0, 1, CHUNK - 1, CHUNK, n - 1, n):
        head, tail = payload[:cut], payload[cut:]
        assert tfmt.adler32_combine(
            zlib.adler32(head), zlib.adler32(tail), len(tail)
        ) == zlib.adler32(payload), cut


def test_a_payload_deflate_cannot_shorten_is_stored_whatever_its_chunks():
    noise = np.random.default_rng(42).bytes(2 * CHUNK + 4096)
    d = tfmt.FrameDeflater(LEVELS["checkpoint"])
    d.feed(noise[:CHUNK + 7])
    d.feed(noise[CHUNK + 7:])
    assert d.finish() == (0, noise)
    assert d.chunks == 3


def test_the_levels_are_the_checkpoints_and_the_traces():
    assert LEVELS == {"trace": 6, "checkpoint": 1}
    assert tfmt.FrameDeflater().compresslevel == tfmt.COMPRESSLEVEL


def test_a_writer_refuses_a_frame_deflated_at_another_level(tmp_path):
    """What keeps the worker's frames and the flush's at one level: a
    journal is byte for byte the same whichever path wrote it."""
    d = tfmt.FrameDeflater(LEVELS["trace"])
    d.feed(b"payload " * 64)
    path = str(tmp_path / "w.ckpt")
    with tfmt.TraceWriter(path, compresslevel=LEVELS["checkpoint"]) as w:
        with pytest.raises(ValueError, match="another level"):
            w.write_snapshot("s", "fp", None, deflated=d)
        size = w.bytes_out
    assert os.path.getsize(path) == size  # nothing of the frame landed


def test_a_journal_of_either_level_restores_and_continues_the_chain(
    served, tmp_path, monkeypatch
):
    """A journal as the commit before ISSUE 34 wrote it (every frame
    ``zlib.compress(payload, 6)``) and one at the checkpoint's level,
    from one session state: the same payloads frame for frame, and
    each, loaded, serves the chain's next tick to the same plan, prices
    and next journal. Readers are blind to the level, so a rolling
    restart and a handoff between processes of both versions work."""
    served.tick()
    session = _session(served)
    ours = _journal(served.server.servicer.ckpt, served.sid)
    assert ours == _sequential(session, tmp_path / "ours")
    with monkeypatch.context() as m:
        m.setattr(ckpt_mod, "CKPT_COMPRESSLEVEL", LEVELS["trace"])
        theirs = _sequential(session, tmp_path / "theirs")
    assert theirs != ours
    payloads = _payloads_at(ours, LEVELS["checkpoint"])
    assert payloads == _payloads_at(theirs, LEVELS["trace"])
    assert [kind for kind, _ in payloads] == WHOLE
    served.tick()
    rows = served.rows
    p_delta = {k: v[rows] for k, v in served.p_cols.items()}
    none = np.zeros(0, np.int32)
    nexts = []
    for name, journal in (("ours", ours), ("theirs", theirs)):
        loaded = _loads(journal, served.sid, tmp_path / f"load-{name}")
        assert loaded.tick == session.tick - 1
        loaded.apply_delta(rows, p_delta, none, {})
        p4t, _t4p, price = loaded.solve()
        assert loaded.arena.last_stats["cold"] is False
        np.testing.assert_array_equal(p4t, served.plan)
        with loaded.lock:
            loaded.tick += 1
            loaded.last_p4t = p4t
        nexts.append(
            (price, _sequential(loaded, tmp_path / f"next-{name}"))
        )
    (price_a, next_a), (price_b, next_b) = nexts
    np.testing.assert_array_equal(price_a, price_b)
    assert next_a == next_b
    # and the next journal is the served session's, column for column
    # and array for array (its META holds the servicer's dedup cursor,
    # which a session driven by hand does not advance)
    served_next = _payloads_at(
        _journal(served.server.servicer.ckpt, served.sid),
        LEVELS["checkpoint"],
    )
    assert _payloads_at(next_a, LEVELS["checkpoint"])[1:] == served_next[1:]


def test_a_recorded_workload_trace_is_still_written_at_the_traces_level(
    tmp_path,
):
    """Traces are written once, archived and replayed: bytes at rest
    are what they pay for, so the recorder and the synthesiser keep
    ``trace/format.COMPRESSLEVEL`` whatever a checkpoint is written
    at."""
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.trace.recorder import TraceRecorder
    from protocol_tpu.trace.synth import (
        synth_providers,
        synth_requirements,
        synth_trace,
    )

    rng = np.random.default_rng(34)
    ep, er = synth_providers(rng, ROWS), synth_requirements(rng, ROWS)
    recorded = str(tmp_path / "recorded.trace")
    recorder = TraceRecorder(recorded)
    p4t = rng.permutation(ROWS).astype(np.int32)
    price = rng.random(ROWS).astype(np.float32)
    for n in range(3):
        ep.price[n] += 1.0
        recorder.record_solve(
            ep, er, CostWeights(), "native-mt", 64, 0.02, 0, p4t,
            price=price,
        )
    recorder.close()
    synthetic = synth_trace(
        str(tmp_path / "synthetic.trace"), n_providers=ROWS, n_tasks=ROWS,
        ticks=3, churn=0.03, seed=34,
    )
    for path, kinds in (
        (recorded, {tfmt.KIND_META, tfmt.KIND_SNAPSHOT, tfmt.KIND_DELTA,
                    tfmt.KIND_OUTCOME}),
        (synthetic, {tfmt.KIND_META, tfmt.KIND_SNAPSHOT, tfmt.KIND_DELTA}),
    ):
        with open(path, "rb") as fh:
            data = fh.read()
        frames = _raw_frames(data)
        assert kinds <= {kind for kind, _f, _b in frames}
        assert any(flags & tfmt._FLAG_DEFLATE for _k, flags, _b in frames)
        _payloads_at(data, LEVELS["trace"])
        # and not by accident of a payload both levels pack alike
        big = max(frames, key=lambda f: len(f[2]))
        assert big[2] != zlib.compress(
            zlib.decompress(big[2]), LEVELS["checkpoint"]
        )


def test_sessions_share_one_worker_and_every_journal_is_whole(tmp_path):
    """More sessions than the one worker can serve at once, ticking
    from threads of their own under a short switch interval: a flush
    takes its own session's prefix or none (never another's, never
    half of one), so each journal is the sequential one."""
    _share_one_worker(tmp_path, n_sessions=6, ticks=4, rows=64, top_k=16)


def test_sessions_share_one_worker_and_every_journal_in_chunks_is_whole(
    tmp_path,
):
    """The same where an ARENA frame is more than one chunk: the
    sessions' chunks share the one DEFLATE pool as well."""
    ckpt = _share_one_worker(
        tmp_path, n_sessions=3, ticks=3, rows=BIG_ROWS, top_k=64
    )
    assert ckpt.chunks > ckpt.flushes * len(WHOLE)


def _share_one_worker(tmp_path, n_sessions, ticks, rows, top_k):
    from protocol_tpu.ops.cost import CostWeights
    from protocol_tpu.proto import wire
    from protocol_tpu.services.session_store import (
        SolveSession,
        make_solve_arena,
    )
    from protocol_tpu.trace.synth import synth_providers, synth_requirements

    ckpt = SessionCheckpointer(str(tmp_path / "shared"))
    refs = SessionCheckpointer(str(tmp_path / "refs"))
    sessions = []
    for i in range(n_sessions):
        rng = np.random.default_rng(100 + i)
        p_cols = wire.canon_columns(
            synth_providers(rng, rows), wire.P_WIRE_DTYPES
        )
        r_cols = wire.canon_columns(
            synth_requirements(rng, rows), wire.R_WIRE_DTYPES
        )
        session = SolveSession(
            session_id=f"s{i}@t", fingerprint=f"fp{i}",
            weights=CostWeights(), kernel="jax", threads=0, top_k=top_k,
            p_cols=p_cols, r_cols=r_cols, n_providers=rows, n_tasks=rows,
            arena=make_solve_arena("jax", k=top_k, threads=0),
        )
        with session.lock:
            session.last_p4t = session.solve()[0]
        sessions.append((session, rng))
    failures, outcomes = [], []

    def drive(session, rng):
        try:
            for _ in range(ticks):
                chosen = np.sort(rng.choice(rows, 2, replace=False))
                with session.lock:
                    ckpt.arm_locked(session)
                    session.apply_delta(
                        chosen.astype(np.int32),
                        {
                            k: (
                                rng.uniform(0.5, 9.0, 2).astype(v.dtype)
                                if k == "price" else v[chosen]
                            )
                            for k, v in session.p_cols.items()
                        },
                        np.zeros(0, np.int32), {},
                    )
                    session.last_p4t = session.solve()[0]
                    session.tick += 1
                    assert ckpt.flush_locked(session)
                    outcomes.append(ckpt.last_flush["prefix"])
                    assert refs.flush_locked(session)
                    assert _journal(ckpt, session.session_id) == _journal(
                        refs, session.session_id
                    )
        except BaseException as e:  # noqa: BLE001 - reported below
            failures.append(repr(e))
            raise

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=drive, args=pair) for pair in sessions
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=200)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures
    assert len(outcomes) == n_sessions * ticks
    assert set(outcomes) <= {"hit", "miss"} and "hit" in outcomes
    assert ckpt.flush_failures == 0 and not ckpt._jobs
    return ckpt
