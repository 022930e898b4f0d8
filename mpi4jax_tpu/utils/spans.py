"""Host spans kept in memory: what a program's host code was doing, when.

A :class:`Recorder` belongs to the object whose host code it follows (a
``models.shallow_water.SolverJob`` owns one and hands it to every save
it starts).  ``recorder.span(name, ...)`` is a context manager: it reads
``time.perf_counter_ns()`` on entry and on exit and keeps one
:class:`Span`, and it is a ``jax.profiler.TraceAnnotation`` of the same
name under the recorder's prefix, so a run wrapped in
``jax.profiler.trace(dir)`` shows the same spans on the profiler's own
timeline beside the device's operations.  Always on: nothing turns it
off, and the list is bounded instead (the newest ``bound`` spans stay,
``dropped`` counts those that went).

    with recorder.span("job/fetch", key=step, bytes=n) as fetch:
        ...
    waited_s += fetch.seconds

What jax builds is the process's, not a job's (its caches are), and so
is the recorder of it: ``builds``.  This module listens to
``jax.monitoring`` from its first import on, and every trace of a
jitted function, every lowering of it to MLIR and every compile (or
load from the persistent cache) that jax reports is a finished span
there, ``build/trace``, ``build/lower`` and ``build/compile``, with
``program`` (jax's ``fun_name``) among its ``counts``; a
``build/compile`` says besides whether the persistent cache was
``asked``, whether it ``cached`` the executable (then ``retrieval_s``
is the cache's own time for the read: a load that is slow is a slow
cache, one on a network mount say, and not a large program) and
whether an entry was ``written``.  jax reports a duration when the work has ended, so such a
span is recorded after the fact (:meth:`Recorder.record`): it ends at
``perf_counter_ns()`` in the callback and begins the reported seconds
before that; no second clock comes in, and it is no
``TraceAnnotation``.  What the program imports in order to build
(``build/import``, with ``module``) is a ``span()`` of the same
recorder.  Nothing is recorded round a call of a compiled program: a
process that builds nothing gains no span.

Traces nest (the trace of a multistep holds the traces of the jitted
functions it calls, and an import that happened under it), so a sum
over ``builds`` counts seconds twice: take a span's self time, each
moment given to the innermost span open on its thread then.  A build's
``cause`` is the span open on its thread *in this recorder*, a
``build/import`` or nothing.  A build under a job's ``job/compile`` or
``job/resume`` lies in another recorder, the job's; both are on
``perf_counter_ns()`` and both carry the thread's name, and
containment in time on one thread is what says whose a build was.
"""

import collections
import dataclasses
import itertools
import threading
import time

import jax
from jax import monitoring

# Every span of a whole run of the benchmark's restarted job with room
# to spare: 1,222 a save of 606 pieces (a fetch and a write a piece),
# 1,215 a resume, about 10,000 on the window's job by the end of a run.
BOUND = 1 << 15


@dataclasses.dataclass(slots=True)
class Span:
    """One span, and the ``with`` that times it.  ``thread``: the name
    of the thread it ran on.  ``cause``: the ``id`` of the span that
    caused it: the span open on the same thread when it was made, or,
    across threads, the one that handed the work over.  ``key``: what
    the spans of one request share (a job's: the model step a snapshot
    or a save holds).  ``counts``: whatever else its caller gave
    (``bytes``, ``calls``, ``program``)."""

    name: str
    id: int
    thread: str
    cause: int
    key: object
    counts: dict
    start_ns: int = 0
    end_ns: int = 0
    _recorder: object = dataclasses.field(default=None, repr=False)
    _annotation: object = dataclasses.field(default=None, repr=False)

    @property
    def seconds(self):
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self):
        self._annotation.__enter__()
        self._recorder._stack().append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        recorder, annotation = self._recorder, self._annotation
        self._recorder = self._annotation = None
        recorder._stack().pop()
        recorder._keep(self)
        return annotation.__exit__(*exc)


class Recorder:
    def __init__(self, prefix="", bound=BOUND):
        self.prefix, self.bound = prefix, bound
        self.dropped = 0
        self._spans = collections.deque(maxlen=bound)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def span(self, name, key=None, cause=None, **counts):
        """A :class:`Span` to be entered by ``with``, which gives it
        back.  ``cause`` defaults to the span open on this thread."""
        stack = self._stack()
        if cause is None and stack:
            cause = stack[-1].id
        return Span(
            name, next(self._ids), threading.current_thread().name, cause, key,
            counts, _recorder=self, _annotation=jax.profiler.TraceAnnotation(
                self.prefix + name, key=key, **counts))

    def record(self, name, seconds, key=None, cause=None, **counts):
        """A finished :class:`Span` for what someone else has timed: it
        ends now and began ``seconds`` ago, on this thread.  ``cause``
        defaults to the span open on this thread.  No ``TraceAnnotation``
        (the profiler cannot be told of what is over)."""
        end = time.perf_counter_ns()
        stack = self._stack()
        if cause is None and stack:
            cause = stack[-1].id
        span = Span(name, next(self._ids), threading.current_thread().name,
                    cause, key, counts, end - round(seconds * 1e9), end)
        self._keep(span)
        return span

    def spans(self):
        """The finished spans, in the order they ended."""
        with self._lock:
            return list(self._spans)

    def _stack(self):  # the spans open on this thread, outermost first
        return self._local.__dict__.setdefault("stack", [])

    def _keep(self, span):
        with self._lock:
            self.dropped += len(self._spans) == self.bound
            self._spans.append(span)


# -- what jax builds: the process's recorder ------------------------------

TRACE, LOWER, COMPILE, IMPORT = (
    "build/trace", "build/lower", "build/compile", "build/import")
_BUILDS = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE = {  # the persistent cache's events, all inside a backend compile
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "cached",
    "/jax/compilation_cache/cache_misses": "written",
}

builds = Recorder("mpi4jax_tpu.")
_compiling = threading.local()  # the cache's events since this thread's last compile


def _on_event(event, **_):
    if event in _CACHE:
        seen = _compiling.__dict__
        if _CACHE[event] == "asked":  # a compile's first: what one that raised left goes
            seen.clear()
        seen[_CACHE[event]] = True


def _on_duration(event, seconds, fun_name=None, **_):
    if event == _RETRIEVAL:
        _compiling.retrieval_s = seconds
    elif event in _BUILDS:
        counts, name = {"program": fun_name}, _BUILDS[event]
        if name == COMPILE:
            seen = _compiling.__dict__
            counts.update({flag: seen.pop(flag, False) for flag in _CACHE.values()},
                          retrieval_s=seen.pop("retrieval_s", 0.0))
        builds.record(name, seconds, **counts)


monitoring.register_event_listener(_on_event)
monitoring.register_event_duration_secs_listener(_on_duration)
