"""The scheduler's three per-layer readers (``admit_wait_ms.p90``,
``prefill_span_ms.p90``, ``prefill_passed_share``) on made-up runs: what
each reads from the program's ``Request`` stamps and counters, the
profiler's writing pause taken out of a span that holds it, and nothing
to read from a program without them."""

import types

import pytest

from bench_testlib import BENCH
from harness import spec
from harness.result import Run
from harness.window import Record, Window


def reader(name):
    return spec.metric_reader(BENCH, name)


def request(sub=None, adm=None, done=None, steps=0, chunks=0):
    return types.SimpleNamespace(submitted_at=sub, admitted_at=adm,
                                 prefilled_at=done, prefill_steps=steps,
                                 prefill_chunks=chunks, token_times=[])


def make_run(reqs, trace=None):
    records = [Record(req=q, due=0.0, submitted=0.0) for q in reqs]
    win = Window(t0=0.0, t1=60.0, records=records, residents=[], steps=[],
                 compiles=[], trace=trace)
    return Run(config={}, window=win, peaks={}, memory_peak_bytes=0)


# Ten requests: waits of 1..10 ms, prefill spans of 100..1000 ms.
TEN = [request(sub=10.0 * i, adm=10.0 * i + 1e-3 * (i + 1),
               done=10.0 * i + 1e-3 * (i + 1) + 0.1 * (i + 1),
               steps=4, chunks=2 if i % 2 else 4)
       for i in range(10)]


def test_admit_wait_is_submit_to_admission():
    # numpy's linear p90 of 1..10 is 9.1
    assert reader("admit_wait_ms.p90")(make_run(TEN)) == pytest.approx(9.1)


def test_prefill_span_is_admission_to_last_chunk():
    assert reader("prefill_span_ms.p90")(make_run(TEN)) == \
        pytest.approx(910.0)


def test_passed_share_counts_steps_that_ran_another_chunk():
    # five requests passed over twice in four steps, five never: 10 of 40
    assert reader("prefill_passed_share")(make_run(TEN)) == \
        pytest.approx(25.0)


def test_profiler_pause_inside_a_span_is_taken_out():
    """The loop stood still 2 s while the profiler wrote its trace: first
    inside the request's wait for admission, then inside its prefill.
    Each span is read without the pause."""
    q = request(sub=10.0, adm=13.5, done=16.0, steps=3, chunks=3)
    trace = {"start": 5.0, "stop_called": 11.0, "stop": 13.0}
    run = make_run([q], trace=trace)
    assert reader("admit_wait_ms.p90")(run) == pytest.approx(1500.0)
    assert reader("prefill_span_ms.p90")(run) == pytest.approx(2500.0)
    q.admitted_at = 10.5          # the pause now falls in the prefill only
    assert reader("admit_wait_ms.p90")(run) == pytest.approx(500.0)
    assert reader("prefill_span_ms.p90")(run) == pytest.approx(3500.0)
    assert reader("prefill_passed_share")(run) == 0.0


def test_unfinished_prompts_are_left_out():
    """A request still prefilling (or never admitted) has no span to read;
    its counters stay out of the share too."""
    waiting = request(sub=1.0)
    prefilling = request(sub=1.0, adm=1.002, steps=9, chunks=1)
    done = request(sub=2.0, adm=2.004, done=2.5, steps=4, chunks=3)
    run = make_run([waiting, prefilling, done])
    assert reader("admit_wait_ms.p90")(run) == pytest.approx(3.8)
    assert reader("prefill_span_ms.p90")(run) == pytest.approx(496.0)
    assert reader("prefill_passed_share")(run) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["admit_wait_ms.p90",
                                  "prefill_span_ms.p90",
                                  "prefill_passed_share"])
def test_a_program_without_the_stamps_gives_nothing(name):
    """A ``Request`` of a program that stamps nothing: the reader returns
    None and the result line leaves the metric out."""
    old = types.SimpleNamespace(token_times=[1.0], tokens=[3])
    assert reader(name)(make_run([old])) is None
    assert reader(name)(make_run([])) is None
