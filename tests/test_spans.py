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


# -- a span recorded after the fact ---------------------------------------


def test_a_recorded_span_ends_now_and_began_its_seconds_ago():
    trace = Recorder("mpi4jax_tpu.")
    before = time.perf_counter_ns()
    s = trace.record("build/trace", 0.25, key=3, program="multistep")
    after = time.perf_counter_ns()
    assert trace.spans() == [s] and trace.dropped == 0
    assert (s.name, s.key, s.cause) == ("build/trace", 3, None)
    assert s.counts == {"program": "multistep"}
    assert s.thread == threading.current_thread().name
    # one clock: the end is read in the call, the start is the seconds before it
    assert before <= s.end_ns <= after
    assert s.end_ns - s.start_ns == 250_000_000 and s.seconds == 0.25
    # nothing is left open by it
    with trace.span("next") as nxt:
        pass
    assert nxt.cause is None


def test_a_recorded_span_is_caused_by_the_span_open_on_its_thread():
    trace = Recorder()
    with trace.span("build/import") as outer:
        inside = trace.record("build/trace", 1e-3)
        said = trace.record("build/lower", 1e-3, cause=77)
    alone = trace.record("build/trace", 1e-3)
    assert (inside.cause, said.cause, alone.cause) == (outer.id, 77, None)
    assert trace.spans() == [inside, said, outer, alone]
    assert len({s.id for s in trace.spans()}) == 4

    seen = []
    worker = threading.Thread(
        target=lambda: seen.append(trace.record("build/compile", 1e-3)), name="other")
    with trace.span("job/compile"):
        worker.start()
        worker.join(30)
    # the other thread's open span is none of the recording thread's business
    assert seen[0].cause is None and seen[0].thread == "other"


def test_recorded_spans_count_against_the_bound():
    trace = Recorder(bound=3)
    for i in range(5):
        trace.record("build/trace", 0.0, key=i)
    with trace.span("s", key=5):
        pass
    assert [s.key for s in trace.spans()] == [3, 4, 5] and trace.dropped == 3


def test_a_recorded_span_is_no_trace_annotation(monkeypatch):
    def annotation(*args, **kwargs):
        raise AssertionError("what is over cannot be annotated")

    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", annotation)
    assert Recorder("p.").record("build/trace", 0.5).seconds == 0.5


# -- what jax builds: the process's recorder ------------------------------


def _built_since(mark, thread=None):
    thread = thread or threading.current_thread().name
    return [s for s in spans.builds.spans() if s.id > mark and s.thread == thread]


def _mark():
    return spans.builds.record("mark", 0.0).id


def test_a_first_call_is_traced_lowered_and_compiled_and_a_second_is_not():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def only_here_once(x):
        return x * 3 + 1

    x = jnp.arange(4.0)  # built before the mark: its own small programs
    mark, before = _mark(), time.perf_counter_ns()
    only_here_once(x).block_until_ready()
    after = time.perf_counter_ns()
    got = _built_since(mark)
    mine = [s for s in got if "only_here_once" in (s.counts.get("program") or "")]
    assert [s.name for s in mine] == ["build/trace", "build/lower", "build/compile"]
    traced, lowered, compiled = mine
    assert traced.counts == {"program": "only_here_once"}
    assert lowered.counts == {"program": "jit(only_here_once)"}
    assert compiled.counts["program"] == "jit(only_here_once)"
    assert set(compiled.counts) == {
        "program", "asked", "cached", "written", "retrieval_s"}
    assert compiled.counts["cached"] in (True, False)
    assert compiled.counts["retrieval_s"] >= 0.0
    # on the benchmark's clock, in order, each as long as jax said it was
    assert (before <= traced.start_ns <= traced.end_ns <= lowered.end_ns
            <= compiled.start_ns <= compiled.end_ns <= after)
    assert all(s.seconds > 0 and s.cause is None for s in mine)
    # built: a second call builds nothing
    mark = _mark()
    only_here_once(x).block_until_ready()
    assert _built_since(mark) == []


def test_a_nested_jits_trace_lies_inside_its_callers():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner_of_the_pair(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer_of_the_pair(x):
        return inner_of_the_pair(x) + inner_of_the_pair(x + 1)

    x = jnp.arange(8.0)
    mark = _mark()
    outer_of_the_pair(x).block_until_ready()
    got = _built_since(mark)
    traces = {s.counts["program"]: s for s in got if s.name == "build/trace"}
    inner, outer = traces["inner_of_the_pair"], traces["outer_of_the_pair"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    # the caller's span ended later and so was kept later; a plain sum of
    # the two counts the inner trace twice
    assert inner.id < outer.id and inner.seconds < outer.seconds
    # one program is lowered and compiled: the caller's
    assert [s.counts["program"] for s in got if s.name != "build/trace"] == [
        "jit(outer_of_the_pair)"] * 2


def test_two_threads_building_at_once_keep_their_own():
    """Each thread's builds carry its name, and what the cache said on
    one thread is not told of the other's compile."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(16.0)
    mark = _mark()
    start = threading.Barrier(2)

    def work(k):
        @jax.jit
        def built_on_a_thread(x):
            return (x + k) * k

        start.wait(30)
        built_on_a_thread(x).block_until_ready()

    workers = [threading.Thread(target=work, args=(k,), name=f"builder-{k}")
               for k in (2, 3)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(120)
    assert not any(w.is_alive() for w in workers)
    for w in workers:
        mine = [s for s in _built_since(mark, w.name)
                if "built_on_a_thread" in (s.counts.get("program") or "")]
        assert [s.name for s in mine] == ["build/trace", "build/lower", "build/compile"]
        assert mine[0].end_ns <= mine[1].end_ns <= mine[2].start_ns
    assert _built_since(mark) == []  # nothing of theirs on this thread


def test_the_caches_events_are_told_of_the_compile_on_their_thread():
    """The listeners as jax calls them: a hit and its retrieval time on
    one thread end up on that thread's next ``build/compile`` and on no
    other, and are used up by it."""
    from jax import monitoring

    compile_event = "/jax/core/compile/backend_compile_duration"

    def hit_then_compile():
        monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        gate.wait(30)
        monitoring.record_event_duration_secs(compile_event, 0.5, fun_name="jit(hit)")

    gate = threading.Event()
    mark = _mark()
    other = threading.Thread(target=hit_then_compile, name="loader")
    other.start()
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event_duration_secs(compile_event, 2.0, fun_name="jit(miss)")
    monitoring.record_event_duration_secs(compile_event, 1.0, fun_name="jit(bare)")
    monitoring.record_event_duration_secs("/jax/some/other_duration", 9.0)
    gate.set()
    other.join(30)
    (miss, bare), (hit,) = _built_since(mark), _built_since(mark, "loader")
    assert miss.counts == {"program": "jit(miss)", "asked": True, "cached": False,
                           "written": True, "retrieval_s": 0.0}
    assert bare.counts == {"program": "jit(bare)", "asked": False, "cached": False,
                           "written": False, "retrieval_s": 0.0}
    assert hit.counts == {"program": "jit(hit)", "asked": True, "cached": True,
                          "written": False, "retrieval_s": 0.125}
    assert (miss.seconds, bare.seconds, hit.seconds) == (2.0, 1.0, 0.5)


def test_what_a_compile_that_raised_heard_is_not_told_of_the_next():
    """A compile that raises reports no duration: what the cache said
    during it goes when the thread's next compile asks the cache."""
    from jax import monitoring

    mark = _mark()
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    # ... and the load raised.  The next compile on this thread:
    monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 1.0, fun_name="jit(next)")
    (compiled,) = _built_since(mark)
    assert compiled.counts == {"program": "jit(next)", "asked": True, "cached": False,
                               "written": True, "retrieval_s": 0.0}


def test_the_imports_a_build_needs_are_recorded_once():
    """In a fresh interpreter (a test worker's recorder may have let its
    oldest spans go): the package's import is a span from after jax's
    import (not the package's cost: a process that has jax pays it once)
    to after the package's last, Pallas's is one inside the trace that
    asked for it and a span of its own, and neither is recorded a second
    time."""
    import os
    import pathlib
    import subprocess

    root = pathlib.Path(spans.__file__).resolve().parents[2]
    code = """
import importlib, sys, time
looked_up = {}
class Looks:
    def find_spec(self, name, path=None, target=None):
        looked_up.setdefault(name, time.perf_counter_ns())
sys.meta_path.insert(0, Looks())
before = time.perf_counter_ns()
import mpi4jax_tpu
after = time.perf_counter_ns()
import jax, jax.numpy as jnp
from mpi4jax_tpu.models import sw_kernels
from mpi4jax_tpu.utils import spans

@jax.jit
def asks_for_pallas(x):
    sw_kernels.pallas()
    return x + 1

asks_for_pallas(jnp.zeros(3))
sw_kernels.pallas()
importlib.import_module("mpi4jax_tpu")
got = spans.builds.spans()
imports = {s.counts["module"]: s for s in got if s.name == spans.IMPORT}
assert sorted(imports) == ["jax.experimental.pallas", "mpi4jax_tpu"], imports
assert sum(s.name == spans.IMPORT for s in got) == 2
package, pallas = imports["mpi4jax_tpu"], imports["jax.experimental.pallas"]
assert before <= package.start_ns <= package.end_ns <= after
# it began once jax was imported, with the package's own first import
jax_began, own_began = looked_up["jax"], looked_up["mpi4jax_tpu.utils"]
assert jax_began < package.start_ns <= own_began
assert own_began - package.start_ns < package.start_ns - jax_began
assert (after - package.end_ns) * 20 < after - before
(trace,) = [s for s in got if s.counts.get("program") == "asks_for_pallas"
            and s.name == spans.TRACE]
assert trace.start_ns <= pallas.start_ns <= pallas.end_ns <= trace.end_ns
assert package.cause is None and pallas.cause is None and spans.builds.dropped == 0
print("recorded once")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(root) + os.pathsep + env.get("PYTHONPATH", "")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith("recorded once")


def test_one_module_listens_to_jax_and_nothing_turns_it_off():
    """One tracing system: ``utils/spans.py`` alone registers listeners
    on ``jax.monitoring``, once, and ``chip_smoke.py`` none."""
    import inspect
    import pathlib

    from jax._src import monitoring

    assert monitoring._event_listeners.count(spans._on_event) == 1
    assert monitoring._event_duration_secs_listeners.count(spans._on_duration) == 1
    root = pathlib.Path(spans.__file__).resolve().parents[2]
    registering = sorted(
        str(path.relative_to(root))
        for path in [*root.glob("mpi4jax_tpu/**/*.py"), root / "chip_smoke.py"]
        if "register_event" in path.read_text())
    assert registering == ["mpi4jax_tpu/utils/spans.py"]
    assert list(inspect.signature(Recorder.record).parameters) == [
        "self", "name", "seconds", "key", "cause", "counts"]
