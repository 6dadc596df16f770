"""Scheduler property tests (ISSUE 4, satellite 2): random arrival /
length streams driven through the pure-python SlotScheduler (no jax —
the same object the engine drives with real jitted steps).

Invariants: no slot leaks, FCFS admission order preserved (no
starvation), every request completes with exactly min(steps-to-eos,
max_tokens) tokens, total decode ticks >= the longest request.
"""

import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.serving.request import Request
from repro.serving.scheduler import SlotScheduler

pytestmark = pytest.mark.serving


def _simulate(n_slots: int, specs: list) -> tuple[SlotScheduler, dict, int]:
    """Drive a full drain.  specs: per request (arrival_tick, max_tokens,
    eos_step | None).  The scripted model emits token ``eos_id`` when a
    request has already emitted ``eos_step`` tokens, else a counter."""
    eos_id = 10**9
    sched = SlotScheduler(n_slots)
    pending = sorted(range(len(specs)), key=lambda i: (specs[i][0], i))
    finished = {}
    tick = 0
    decode_ticks = 0
    submitted = 0
    while submitted < len(specs) or sched.has_work():
        for i in list(pending):
            if specs[i][0] <= tick:
                arrival, max_tokens, eos_step = specs[i]
                sched.submit(Request(rid=i, prompt=(1,), max_tokens=max_tokens,
                                     eos_id=eos_id))
                pending.remove(i)
                submitted += 1
        sched.admit()
        sched.check_invariants()
        if sched.active:
            token_by_slot = {}
            for slot, tracker in sched.active.items():
                eos_step = specs[tracker.req.rid][2]
                emit_eos = eos_step is not None and len(tracker.tokens) == eos_step
                token_by_slot[slot] = eos_id if emit_eos else len(tracker.tokens)
            for tracker in sched.record_tokens(token_by_slot):
                finished[tracker.req.rid] = tracker
            decode_ticks += 1
        sched.check_invariants()
        tick += 1
        assert tick < 10_000, "scheduler failed to drain"
    return sched, finished, decode_ticks


@given(
    st.integers(min_value=1, max_value=5),
    st.lists(st.integers(min_value=0, max_value=6), min_size=1, max_size=14),
    st.lists(st.integers(min_value=1, max_value=9), min_size=1, max_size=14),
    st.lists(st.integers(min_value=0, max_value=12), min_size=1, max_size=14),
)
@settings(deadline=None,
          suppress_health_check=[HealthCheck.too_slow, HealthCheck.filter_too_much])
def test_random_streams_preserve_all_invariants(n_slots, arrivals, lengths, eos_raw):
    n = min(len(arrivals), len(lengths), len(eos_raw))
    specs = []
    for i in range(n):
        # eos beyond max_tokens (or the sentinel > 9) means "never"
        eos = eos_raw[i] if eos_raw[i] < lengths[i] else None
        specs.append((arrivals[i], lengths[i], eos))

    sched, finished, decode_ticks = _simulate(n_slots, specs)

    # no slot leaks: the drained pool is whole again
    assert sched.free_slots == n_slots and not sched.active and sched.pending == 0
    # no starvation: admissions happened in exact submission order
    assert sched.admission_log == sched._submit_log
    assert sorted(finished) == list(range(n))
    expected_tokens = []
    for i, (_, max_tokens, eos) in enumerate(specs):
        expect = max_tokens if eos is None else min(eos + 1, max_tokens)
        expected_tokens.append(expect)
        assert len(finished[i].tokens) == expect, (
            f"request {i}: {len(finished[i].tokens)} tokens != {expect}")
        assert finished[i].finished_by == (
            "eos" if eos is not None and eos + 1 <= max_tokens else "max_tokens")
    # the pool can't finish faster than its longest request decodes
    assert decode_ticks >= max(expected_tokens)
    # nor faster than the total work divided over the slots
    assert decode_ticks >= -(-sum(expected_tokens) // n_slots)


def test_admission_is_fcfs_across_retirements():
    """A freed slot must go to the *oldest* queued request, not the newest."""
    sched = SlotScheduler(1)
    for i in range(4):
        sched.submit(Request(rid=i, prompt=(1,), max_tokens=1))
    order = []
    while sched.has_work():
        for t in sched.admit():
            order.append(t.req.rid)
        for t in sched.record_tokens({s: 0 for s in sched.active}):
            pass
    assert order == [0, 1, 2, 3]


def test_slots_reused_lowest_first():
    sched = SlotScheduler(3)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=(1,), max_tokens=2))
    admitted = {t.req.rid: t.slot for t in sched.admit()}
    assert admitted == {0: 0, 1: 1, 2: 2}
    sched.retire(1)
    sched.submit(Request(rid=9, prompt=(1,), max_tokens=1))
    assert [t.slot for t in sched.admit()] == [1]


def test_tracker_rejects_tokens_after_finish():
    sched = SlotScheduler(1)
    sched.submit(Request(rid=0, prompt=(1,), max_tokens=1))
    (tracker,) = sched.admit()
    assert tracker.append(7) is True
    with pytest.raises(AssertionError):
        tracker.append(8)


def test_invalid_construction_rejected():
    with pytest.raises(ValueError):
        SlotScheduler(0)
    with pytest.raises(ValueError):
        Request(rid=0, prompt=(), max_tokens=1)
    with pytest.raises(ValueError):
        Request(rid=0, prompt=(1,), max_tokens=0)
