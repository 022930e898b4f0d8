"""The host spans' recorder (``utils/spans.py``): what a span holds, who
caused it, the bound, and threads."""

import sys
import threading
import time

import pytest

from mpi4jax_tpu.utils import spans
from mpi4jax_tpu.utils.spans import Recorder


def test_a_span_holds_its_times_its_thread_its_key_and_its_counts():
    trace = Recorder("mpi4jax_tpu.")
    before = time.perf_counter_ns()
    with trace.span("job/fetch", key=41, bytes=12, program="snap") as s:
        inside = time.perf_counter_ns()
        assert s.end_ns == 0  # still open
    after = time.perf_counter_ns()
    assert trace.spans() == [s] and trace.dropped == 0
    assert (s.name, s.key, s.cause) == ("job/fetch", 41, None)
    assert s.counts == {"bytes": 12, "program": "snap"}
    assert s.thread == threading.current_thread().name
    # on the clock the benchmark's batches are on
    assert before <= s.start_ns <= inside <= s.end_ns <= after
    assert s.seconds == (s.end_ns - s.start_ns) / 1e9


def test_a_nested_span_is_caused_by_the_span_open_on_its_thread():
    trace = Recorder()
    with trace.span("a") as a:
        with trace.span("b") as b:
            with trace.span("c") as c:
                pass
        with trace.span("d", cause=c.id) as d:  # said outright: that one
            pass
    with trace.span("e") as e:
        pass
    assert (a.cause, b.cause, c.cause, d.cause, e.cause) == (
        None, a.id, b.id, c.id, None)
    assert len({s.id for s in (a, b, c, d, e)}) == 5
    # kept in the order they ended, each inside its cause
    assert trace.spans() == [c, b, d, a, e]
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns <= a.end_ns


def test_a_cause_across_threads_is_the_span_that_handed_the_work_over():
    trace = Recorder()
    done = []

    def work(cause):
        with trace.span("checkpoint/save", cause=cause) as s:
            with trace.span("checkpoint/fetch") as f:
                pass
        done.extend([s, f])

    with trace.span("job/save") as handed:
        worker = threading.Thread(target=work, args=(handed.id,), name="saver")
        worker.start()
        worker.join(30)
    assert not worker.is_alive()
    s, f = done
    assert (s.cause, f.cause) == (handed.id, s.id)
    assert s.thread == f.thread == "saver" != handed.thread
    # the other thread's open span is none of this thread's business
    assert handed.cause is None


def test_the_newest_spans_stay_and_the_rest_are_counted():
    trace = Recorder(bound=4)
    for i in range(10):
        with trace.span("s", key=i):
            pass
    assert [s.key for s in trace.spans()] == [6, 7, 8, 9]
    assert trace.dropped == 6
    # the default holds a whole run of the benchmark's restarted job
    assert Recorder().bound == spans.BOUND >= 2 * 10_000


def test_a_body_that_raises_still_records_and_raises():
    trace = Recorder()
    with pytest.raises(KeyError):
        with trace.span("outer") as outer:
            with trace.span("inner") as inner:
                raise KeyError("x")
    assert trace.spans() == [inner, outer]
    assert 0 < inner.start_ns <= inner.end_ns <= outer.end_ns
    with trace.span("next") as after:  # nothing was left open
        pass
    assert after.cause is None


def test_threads_at_once_lose_no_span_and_share_no_cause():
    """More threads than cores, the interpreter switching often: every
    span is kept once, ids are unique, and a span's cause is a span of
    its own thread."""
    trace = Recorder(bound=1 << 16)
    threads, each = 16, 300
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for i in range(each):
                with trace.span("outer", key=i):
                    with trace.span("inner", key=i):
                        pass

        workers = [threading.Thread(target=work, name=f"w{k}") for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(interval)
    got = trace.spans()
    assert len(got) == 2 * threads * each and trace.dropped == 0
    by_id = {s.id: s for s in got}
    assert len(by_id) == len(got)
    for s in got:
        if s.name == "inner":
            outer = by_id[s.cause]
            assert (outer.thread, outer.name, outer.key) == (s.thread, "outer", s.key)
        else:
            assert s.cause is None


def test_a_span_is_a_trace_annotation_under_the_recorders_prefix(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **stats):
            seen.append(("made", name, stats))

        def __enter__(self):
            seen.append("in")

        def __exit__(self, *exc):
            seen.append("out")

    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", Annotation)
    with Recorder("mpi4jax_tpu.").span("checkpoint/write", key=11, bytes=7) as s:
        assert seen == [("made", "mpi4jax_tpu.checkpoint/write",
                         {"key": 11, "bytes": 7}), "in"]
    assert seen[-1] == "out" and s.end_ns >= s.start_ns > 0


def test_the_recorder_has_no_switch():
    """Always on: nothing in the environment and no argument turns it off."""
    import inspect

    source = inspect.getsource(spans)
    assert "environ" not in source and "getenv" not in source
    assert list(inspect.signature(Recorder).parameters) == ["prefix", "bound"]
    assert list(inspect.signature(Recorder.span).parameters) == [
        "self", "name", "key", "cause", "counts"]
