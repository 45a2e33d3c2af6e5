"""The control and the planted faults that ``correct`` has to fail.

Used by ``control.py`` (on the chip, at a cell's own size) and by the
tests (on the CPU, small); a benchmark run never imports this. Each
fault is planted in the program, underneath the timed path, by swapping
one attribute of the program for as long as the context lasts:

``control``         the configuration's flush-before-ack guarantee
                    broken: the server checkpoints every 1,000,000th
                    tick, so acks go out ahead of their journal
``journal_body_dropped`` the checkpoint is flushed before the ack, but
                    only its META and OUTCOME frames: the columns and
                    the arena's state, the bytes that cost the time,
                    are left out
``state_unchanged`` ``apply_delta`` writes nothing: every tick is
                    solved on the columns of the open
``answer_altered``  ``solve`` swaps the providers of one task pair in
                    two hundred before the plan goes out
``rounds_capped``   every auction phase stops after one round per 64
                    tasks of the pool (128 at 8,192 rows, where a sound
                    tick takes some 4,000); the greedy clean-up seats
                    whoever is still open (a solve that cuts its rounds)
``tail_left_open``  the same cap, and the greedy clean-up left out: the
                    tasks the auction had not seated stay open
``queue_pass_skipped`` no pool is ever said to have a queue, so the
                    queue pass and its reserve are left out and the
                    stall breaker decides which tasks wait (the solve
                    before PR 33; a fault only where tasks outnumber
                    providers, elsewhere the sound path)
"""

from __future__ import annotations

import contextlib
import copy

import numpy as np

FAULTS = ("control", "journal_body_dropped", "state_unchanged",
          "answer_altered", "rounds_capped", "tail_left_open",
          "queue_pass_skipped")
TASKS_PER_ROUND = 64


def control_cell(cell: dict) -> dict:
    out = copy.deepcopy(cell)
    out["config"]["server"]["ckpt_every"] = 1_000_000
    return out


@contextlib.contextmanager
def _swapped(owner, name: str, replacement):
    original = getattr(owner, name)
    setattr(owner, name, replacement(original))
    try:
        yield
    finally:
        setattr(owner, name, original)


def _session():
    from protocol_tpu.services.session_store import SolveSession

    return SolveSession


def state_unchanged(_cell=None):
    def make(_original):
        def apply_delta(self, provider_rows, p_delta, task_rows, r_delta,
                        events=None):
            return 0
        return apply_delta
    return _swapped(_session(), "apply_delta", make)


def answer_altered(_cell=None):
    def make(original):
        def solve(self):
            p4t, t4p, price = original(self)
            p4t = np.array(p4t, copy=True)
            seated = np.flatnonzero(p4t >= 0)
            n = max(seated.size // 200, 1)
            a, b = seated[:n], seated[-n:]
            p4t[a], p4t[b] = p4t[b].copy(), p4t[a].copy()
            return p4t, t4p, price
        return solve
    return _swapped(_session(), "solve", make)


def rounds_capped(cell: dict):
    from protocol_tpu.ops import sparse

    cap = max(int(cell["config"]["n_tasks"]) // TASKS_PER_ROUND, 1)

    def make(original):
        # the phase's host loop takes a segment that ends early for a
        # phase that is done, so capping the segment caps the phase
        def _sparse_auction_phase(*args, max_iters, **kwargs):
            return original(*args, max_iters=min(max_iters, cap), **kwargs)
        return _sparse_auction_phase
    return _swapped(sparse, "_sparse_auction_phase", make)


@contextlib.contextmanager
def tail_left_open(cell: dict):
    from protocol_tpu.ops import sparse

    def make(_original):
        def _greedy_cleanup(cand_provider, cand_cost, owner, p4t):
            return p4t
        return _greedy_cleanup
    with rounds_capped(cell), _swapped(sparse, "_greedy_cleanup", make):
        yield


def queue_pass_skipped(_cell=None):
    from protocol_tpu.ops import sparse

    def make(_original):
        def _queue_reserve(*args, **kwargs):
            return None
        return _queue_reserve
    return _swapped(sparse, "_queue_reserve", make)


@contextlib.contextmanager
def journal_body_dropped(_cell=None):
    from protocol_tpu.trace.format import TraceWriter

    def make(_original):
        def write_nothing(self, *args, **kwargs):
            return None
        return write_nothing
    with _swapped(TraceWriter, "write_snapshot", make), \
            _swapped(TraceWriter, "write_arena", make):
        yield


_PLANTED = {
    "state_unchanged": state_unchanged,
    "answer_altered": answer_altered,
    "rounds_capped": rounds_capped,
    "tail_left_open": tail_left_open,
    "journal_body_dropped": journal_body_dropped,
    "queue_pass_skipped": queue_pass_skipped,
}


@contextlib.contextmanager
def planted(fault: str, cell: dict):
    """Yield the cell to run with ``fault`` in place (``sound`` plants
    nothing)."""
    if fault == "sound":
        yield cell
    elif fault == "control":
        yield control_cell(cell)
    elif fault in _PLANTED:
        with _PLANTED[fault](cell):
            yield cell
    else:
        raise ValueError(f"unknown fault {fault!r}")
